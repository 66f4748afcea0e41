"""Elastic lockstep membership over REAL multi-process gloo groups (r16,
ISSUE 13; lead election r20, ISSUE 17): the fleet that shrinks,
rebalances, rejoins — and now survives its own coordinator.

Acceptance (ISSUE 13):
- ``--chaos peer.kill`` on host 1 → host 0 SHRINKS to a 1-host group
  within the watchdog window and keeps training — no abort, departed rows
  counted, and the survivor's continuation is bit-equal to a clean run
  from the restored checkpoint;
- a restarted host is ADMITTED at an epoch boundary and its first-tick
  weights bit-match the lead's (matching state CRCs on every host);
- zero new collectives per healthy tick with the membership plane ACTIVE
  (``process_allgather`` counted over a real lockstep run, the PR 1/5
  idiom) and zero added host fetches;
- the cross-host compressed-wire bucket (``--wireCodec dict`` on
  multi-host, ROADMAP item 3 REMAINING) trains stats-identically to the
  raw multi-host wire — the agreement rides the existing alignment
  allgather.

Acceptance (ISSUE 17 — kill the LEAD, the last single point of failure):
- ``--chaos peer.kill:uid=0`` kills the lead mid-run → the survivor
  detects the orphaned beacon, WINS the election (deterministic successor
  rule: lowest live uid of the committed view), re-binds the beacon,
  promotes its shadow checkpoint lineage, and keeps training — with a
  continuation BIT-equal to a clean run from its own verified archives;
- the healthy-tick zero-new-collectives law holds at 8-host scale
  (the allgather count IS the tick count with 8 members' columns riding
  it), and an 8-host churn storm (follower kill + lead kill + pauses,
  driven by tools/chaos_fleet.py) forms every epoch with fleet-wide
  CRC-identical resyncs and counted losses.
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "distributed_worker.py")
APP_WORKER = os.path.join(REPO, "tests", "app_worker.py")

NOW_MS = 1785320000000
CLOSED = "http://127.0.0.1:9"  # closed port: telemetry Try paths, no DNS


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


def _write_replay(tmp_path, total: int, seed: int = 5):
    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=total, seed=seed, base_ms=NOW_MS).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")
    return path, statuses


def _elastic_args(path, ck, extra=()):
    return [
        "linear", "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--checkpointDir", str(ck), "--elastic", "on",
        "--lightning", CLOSED, "--twtweb", CLOSED,
    ] + list(extra)


def _spawn_app(pid, nprocs, base, args, env):
    return subprocess.Popen(
        [sys.executable, APP_WORKER, str(pid), str(nprocs), str(base), "2"]
        + args,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )


def _elastic_env(**extra):
    env = dict(
        os.environ, PYTHONPATH=REPO, TWTML_NOW_MS=str(NOW_MS),
        TWTML_LOCKSTEP_TIMEOUT_S="5", TWTML_ELASTIC_RESCUE_GRACE_S="2",
    )
    env.update(extra)
    return env


def _stat_lines(out: str):
    return [ln for ln in out.splitlines() if ln.startswith("count:")]


def test_healthy_elastic_tick_adds_no_collectives_and_no_fetches():
    """The PR 1/5 law with the membership plane ACTIVE: membership columns
    widen the cadence allgather's payload, never its call count, and the
    pooled stats fetch stays one device_get per dispatched batch."""
    base = _free_port_range()
    env = dict(os.environ, PYTHONPATH=REPO)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), "2", str(base), "unit",
             "elastic_count"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=240.0)
            if p.returncode != 0:
                pytest.fail(
                    f"worker failed rc={p.returncode}:\n{stderr[-3000:]}"
                )
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            p.kill()
    for o in outs:
        assert o["terminated"] and not o["failed"]
        assert o["batches"] >= 6
        # ZERO new collectives: the allgather count IS the tick count,
        # membership columns included
        assert o["allgathers"] == o["ticks"], o
        # ZERO added host fetches: one pooled get per dispatched batch
        assert o["device_gets"] == o["batches"] == o["fetch_count"], o
        # a healthy run never transitions
        assert o["epoch"] == 0 and o["members"] == [0, 1]
        assert o["transitions"] == []


def test_peer_kill_shrinks_and_survivor_bitmatches_clean_run(tmp_path):
    """THE shrink acceptance: host 1 hard-dies at lockstep tick 4 (no
    abort broadcast — ``--chaos peer.kill``); host 0 must shrink to a
    1-host epoch within the watchdog window and keep training. No abort,
    departed rows counted, and the survivor's post-shrink trajectory is
    BIT-EQUAL to a clean run started from the restored checkpoint over
    the surviving intake."""
    import shutil
    import threading

    path, statuses = _write_replay(tmp_path, 200)
    ck = tmp_path / "ck"
    ck.mkdir()
    keep = tmp_path / "archives"  # rotation-proof copies of every save
    keep.mkdir()
    stop_copier = threading.Event()

    def copier():
        seen = set()
        while not stop_copier.is_set():
            for f in ck.glob("ckpt-*.npz"):
                if f.name not in seen:
                    try:
                        shutil.copy2(f, keep / f.name)
                        seen.add(f.name)
                    except OSError:
                        pass  # racing the writer's rename; next pass wins
            stop_copier.wait(0.05)

    copier_thread = threading.Thread(target=copier, daemon=True)
    copier_thread.start()

    base = _free_port_range()
    env = _elastic_env()
    args = _elastic_args(path, ck, extra=["--checkpointEvery", "1"])
    lead = _spawn_app(0, 2, base, args, env)
    peer = _spawn_app(1, 2, base, args + ["--chaos", "peer.kill:tick=4"], env)
    try:
        lo, le = lead.communicate(timeout=420.0)
        po, pe = peer.communicate(timeout=60.0)
    finally:
        stop_copier.set()
        copier_thread.join(timeout=5)
    assert peer.returncode == 77, f"peer did not chaos-exit:\n{pe[-2000:]}"
    assert lead.returncode == 0, f"survivor failed:\n{le[-4000:]}"

    # no abort: the survivor SHRANK and completed
    assert "aborting" not in le or "instead of aborting" in le
    assert "elastic epoch 1 formed: 1 host(s) [0]" in le
    assert "intake shard rebalanced: now serving residues [0, 1] of 2" in le
    assert "rows_lost_estimate" in le  # departed rows counted, never silent
    lines = _stat_lines(lo)
    assert lines, "survivor printed no stats"
    # pre-kill global batches are 32 rows (two 16-row host shards); the
    # shrunken epoch's are host 0's 16-row buckets
    assert "count: 96  batch: 32" in lines[2]
    # the run covered everything except the dead host's lost share:
    # host 0 trained its full 100-row shard (statuses[0::2])
    final_count = int(re.findall(r"count: (\d+)", lines[-1])[0])
    assert final_count == 148  # 96 global + host 0's remaining 52

    # ---- bit-equality vs a clean run from the restored checkpoint ------
    # The rescue restored checkpoint step 3 (count=96); the survivor then
    # trained host 0's rows 48.. in 16-row buckets on a 2-device mesh.
    # Rebuild exactly that, in process, from the SAME archive.
    import jax

    from twtml_tpu.checkpoint import Checkpointer
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    resync = re.search(
        r"elastic resync: state from the lead's verified checkpoint "
        r"\(count=(\d+), batches=(\d+), state crc ([0-9a-f]+)\)", le,
    )
    assert resync is not None, "survivor never logged the resync"
    assert int(resync.group(1)) == 96 and int(resync.group(2)) == 3

    from twtml_tpu.apps.common import state_checksum

    ckpt = Checkpointer(str(ck))
    state3, meta3 = Checkpointer(str(keep)).restore(step=3)
    # the restored state the survivor continued from is BIT-equal to the
    # verified step-3 archive: the logged resync CRC is its checksum
    assert resync.group(3) == state_checksum(state3)
    conf = ConfArguments().parse(["--backend", "cpu"])
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    model = ParallelSGDModel.from_conf(conf, mesh).set_initial_weights(state3)
    feat = Featurizer(now_ms=NOW_MS)
    shard0 = statuses[0::2]
    for lo_i in range(48, len(shard0), 16):
        batch = feat.featurize_batch_ragged(
            shard0[lo_i:lo_i + 16], row_bucket=16, unit_bucket=64,
            row_multiple=2,
        )
        model.step(model.pack_for_wire(batch))
    final_state, meta = ckpt.restore()
    assert meta["count"] == 148
    np.testing.assert_array_equal(
        np.asarray(final_state), np.asarray(model.latest_weights),
        err_msg="survivor state is not bit-equal to the clean "
                "run-from-checkpoint",
    )


def test_shrink_replays_rolled_back_rows_from_journal(tmp_path):
    """THE replay-after-shrink acceptance (ISSUE 19, two-process gloo):
    with ``--checkpointEvery 2`` the newest verified archive at the kill
    is batch 2, so the rescue THROWS BATCH 3 AWAY — discarded in-flight
    (its collectives died with the peer) or rolled back by the resync —
    where the pre-journal behavior counted those rows lost. With the
    intake journal on (auto via ``--checkpointDir``), the survivor
    re-ingests its own 16 thrown-away rows from its journal (replayed ==
    discarded+rolled, exactly), and the continuation is BIT-EQUAL to a
    clean run from the step-2 archive over the survivor's rows 32.. —
    zero rows lost to the rescue."""
    import shutil
    import threading

    path, statuses = _write_replay(tmp_path, 200)
    ck = tmp_path / "ck"
    ck.mkdir()
    keep = tmp_path / "archives"  # rotation-proof copies of every save
    keep.mkdir()
    stop_copier = threading.Event()

    def copier():
        seen = set()
        while not stop_copier.is_set():
            for f in ck.glob("ckpt-*.npz"):
                if f.name not in seen:
                    try:
                        shutil.copy2(f, keep / f.name)
                        seen.add(f.name)
                    except OSError:
                        pass  # racing the writer's rename; next pass wins
            stop_copier.wait(0.05)

    copier_thread = threading.Thread(target=copier, daemon=True)
    copier_thread.start()

    base = _free_port_range()
    env = _elastic_env()
    args = _elastic_args(path, ck, extra=["--checkpointEvery", "2"])
    lead = _spawn_app(0, 2, base, args, env)
    peer = _spawn_app(1, 2, base, args + ["--chaos", "peer.kill:tick=4"], env)
    try:
        lo, le = lead.communicate(timeout=420.0)
        po, pe = peer.communicate(timeout=60.0)
    finally:
        stop_copier.set()
        copier_thread.join(timeout=5)
    assert peer.returncode == 77, f"peer did not chaos-exit:\n{pe[-2000:]}"
    assert lead.returncode == 0, f"survivor failed:\n{le[-4000:]}"
    assert "elastic epoch 1 formed: 1 host(s) [0]" in le

    # the rescue threw batch 3 away — past the step-2 archive, it is
    # either a discarded in-flight output (dispatched, never delivered:
    # the dead peer poisoned its collectives) or delivered post-checkpoint
    # progress the resync rolled back; both forms are counted, and the
    # survivor's share is its 16-row batch either way
    resync = re.search(
        r"elastic resync: state from the lead's verified checkpoint "
        r"\(count=(\d+), batches=(\d+), state crc ([0-9a-f]+)\)"
        r"(?: — (\d+) row\(s\) of post-checkpoint progress rolled back)?",
        le,
    )
    assert resync is not None, "survivor never logged the resync"
    assert int(resync.group(1)) == 64 and int(resync.group(2)) == 2
    rolled_share = int(resync.group(4) or 0) // 2  # global rows, 2 hosts
    discarded = sum(
        int(r) for r in re.findall(
            r"elastic rescue: discarded \d+ in-flight.*?\(~(\d+) "
            r"row\(s\)\)", le,
        )
    )
    assert rolled_share + discarded == 16, (rolled_share, discarded)

    # the journal converted the survivor's share into a replay: replayed
    # rows == this host's thrown-away rows, exactly
    replay = re.search(
        r"journal: replayed (\d+) row\(s\) from cursor (\d+) after "
        r"elastic rescue — counters reset to \(count=64, batches=2\); "
        r"recovery is replay-exact, zero rows lost", le,
    )
    assert replay is not None, f"survivor never replayed:\n{le[-4000:]}"
    assert int(replay.group(1)) == rolled_share + discarded == 16
    assert int(replay.group(2)) == 2  # the step-2 archive's cursor stamp

    # ledger: 64 restored + the survivor's rows 32.. of its 100-row shard
    # (the replayed 16, the interrupted tick's 16, then the source tail);
    # only the DEAD host's rolled-back+remaining rows are lost with it
    lines = _stat_lines(lo)
    assert lines, "survivor printed no stats"
    final_count = int(re.findall(r"count: (\d+)", lines[-1])[0])
    assert final_count == 132  # 64 global + host 0's remaining 68

    # ---- bit-equality vs a clean run from the step-2 archive -----------
    import jax

    from twtml_tpu.apps.common import state_checksum
    from twtml_tpu.checkpoint import Checkpointer
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    state2, meta2 = Checkpointer(str(keep)).restore(step=2)
    assert resync.group(3) == state_checksum(state2)
    assert meta2["journal"] == {"cursor": 2, "rows": 32}
    conf = ConfArguments().parse(["--backend", "cpu"])
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    model = ParallelSGDModel.from_conf(conf, mesh).set_initial_weights(state2)
    feat = Featurizer(now_ms=NOW_MS)
    shard0 = statuses[0::2]
    for lo_i in range(32, len(shard0), 16):
        batch = feat.featurize_batch_ragged(
            shard0[lo_i:lo_i + 16], row_bucket=16, unit_bucket=64,
            row_multiple=2,
        )
        model.step(model.pack_for_wire(batch))
    final_state, meta = Checkpointer(str(ck)).restore()
    assert meta["count"] == 132
    np.testing.assert_array_equal(
        np.asarray(final_state), np.asarray(model.latest_weights),
        err_msg="replayed continuation is not bit-equal to the clean "
                "run-from-step-2-archive",
    )


def test_lead_kill_elects_successor_and_bitmatches_clean_run(tmp_path):
    """THE election acceptance (ISSUE 17): the LEAD hard-dies at lockstep
    tick 4 (``--chaos peer.kill:uid=0`` — one fleet-wide spec, the uid
    selector picks the victim). The survivor's wedge report hits an
    ORPHANED beacon (connection refused — a dead lead, not a paused one),
    so it elects: sole candidate, rank 0, re-binds the beacon, promotes
    its standby checkpoint lineage, restores its OWN verified step-3
    archive, and finishes the run as the new lead. No abort, the dead
    lead's departed rows counted, and the survivor's post-election
    trajectory is BIT-EQUAL to a clean run from the promoted archive."""
    import shutil
    import threading

    path, statuses = _write_replay(tmp_path, 200)
    ck = tmp_path / "ck"
    ck.mkdir()
    standby = ck / "standby-u1"  # uid 1's shadow-save lineage
    keep = tmp_path / "archives"  # rotation-proof copies of every save
    keep.mkdir()
    stop_copier = threading.Event()

    def copier():
        seen = set()
        while not stop_copier.is_set():
            for f in standby.glob("ckpt-*.npz"):
                if f.name not in seen:
                    try:
                        shutil.copy2(f, keep / f.name)
                        seen.add(f.name)
                    except OSError:
                        pass  # racing the writer's rename; next pass wins
            stop_copier.wait(0.05)

    copier_thread = threading.Thread(target=copier, daemon=True)
    copier_thread.start()

    base = _free_port_range()
    env = _elastic_env()
    # the SAME command line on every host: the uid selector does the aiming
    args = _elastic_args(path, ck, extra=[
        "--checkpointEvery", "1", "--chaos", "peer.kill:uid=0:tick=4",
    ])
    lead = _spawn_app(0, 2, base, args, env)
    surv = _spawn_app(1, 2, base, args, env)
    try:
        so, se = surv.communicate(timeout=420.0)
        lo, le = lead.communicate(timeout=60.0)
    finally:
        stop_copier.set()
        copier_thread.join(timeout=5)
    assert lead.returncode == 77, f"lead did not chaos-exit:\n{le[-2000:]}"
    assert surv.returncode == 0, f"survivor failed:\n{se[-4000:]}"

    # the survivor ELECTED itself instead of aborting: orphaned beacon
    # detected, bind won, authority promoted, epoch formed without uid 0
    assert "the lead (uid 0) is gone; electing a successor" in se
    assert "uid 1 WON the election (beacon :" in se
    assert "checkpoint authority PROMOTED after lead election" in se
    assert "elastic epoch 1 formed: 1 host(s) [1]" in se
    assert "intake shard rebalanced: now serving residues [0, 1] of 2" in se
    assert "rows_lost_estimate" in se  # the dead lead's share, never silent
    # telemetry ownership stayed with launch-time process 0 (now dead):
    # the survivor's proof lives in its logs and its promoted archives
    assert _stat_lines(so) == []

    # ---- bit-equality vs a clean run from the PROMOTED archive ---------
    # The election restored uid 1's standby step-3 checkpoint (count=96);
    # the survivor then trained host 1's rows 48.. in 16-row buckets.
    import jax

    from twtml_tpu.checkpoint import Checkpointer
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    resync = re.search(
        r"elastic resync: state from the lead's verified checkpoint "
        r"\(count=(\d+), batches=(\d+), state crc ([0-9a-f]+)\)", se,
    )
    assert resync is not None, "survivor never logged the resync"
    assert int(resync.group(1)) == 96 and int(resync.group(2)) == 3

    from twtml_tpu.apps.common import state_checksum

    state3, meta3 = Checkpointer(str(keep)).restore(step=3)
    # the state the new lead continued from is BIT-equal to its own
    # verified step-3 shadow archive: the logged resync CRC is its checksum
    assert resync.group(3) == state_checksum(state3)
    conf = ConfArguments().parse(["--backend", "cpu"])
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    model = ParallelSGDModel.from_conf(conf, mesh).set_initial_weights(state3)
    feat = Featurizer(now_ms=NOW_MS)
    shard1 = statuses[1::2]
    for lo_i in range(48, len(shard1), 16):
        batch = feat.featurize_batch_ragged(
            shard1[lo_i:lo_i + 16], row_bucket=16, unit_bucket=64,
            row_multiple=2,
        )
        model.step(model.pack_for_wire(batch))
    # post-promotion saves continued into the standby directory — it IS
    # the fleet lineage now
    final_state, meta = Checkpointer(str(standby)).restore()
    assert meta["count"] == 148  # 96 global + host 1's remaining 52
    np.testing.assert_array_equal(
        np.asarray(final_state), np.asarray(model.latest_weights),
        err_msg="elected lead's state is not bit-equal to the clean "
                "run-from-promoted-checkpoint",
    )


def test_killed_host_rejoins_with_bitmatching_weights(tmp_path):
    """THE rejoin acceptance: after the shrink, the SAME command line
    restarted parks at the lead's beacon, is admitted at the next epoch
    boundary, and restores the broadcast checkpoint BEFORE its first tick
    — its state CRC matches the lead's resync CRC exactly."""
    path, _statuses = _write_replay(tmp_path, 1600)
    ck = tmp_path / "ck"
    base = _free_port_range()
    env = _elastic_env()
    args = _elastic_args(path, ck, extra=["--checkpointEvery", "4"])
    lead = _spawn_app(0, 2, base, args, env)
    peer = _spawn_app(1, 2, base, args + ["--chaos", "peer.kill:tick=4"], env)
    po, pe = peer.communicate(timeout=120.0)
    assert peer.returncode == 77
    time.sleep(6.0)  # let the rescue land; the lead trains on alone
    rejoiner = _spawn_app(1, 2, base, args, env)
    lo, le = lead.communicate(timeout=600.0)
    ro, re_ = rejoiner.communicate(timeout=300.0)
    assert lead.returncode == 0, f"lead failed:\n{le[-4000:]}"
    assert rejoiner.returncode == 0, f"rejoiner failed:\n{re_[-4000:]}"

    assert "parking this host (uid 1) for admission" in re_
    assert "proposing epoch 2 with members [0, 1] (join)" in le
    assert "elastic epoch 2 formed: 2 host(s) [0, 1]" in le
    assert "joined a live replay-sharded run as a hot standby" in re_

    # first-tick weights bit-match: the lead's admission-boundary resync
    # CRC equals the rejoiner's post-broadcast sync CRC
    lead_crcs = re.findall(r"elastic resync: .* state crc ([0-9a-f]+)", le)
    join_crcs = re.findall(
        r"multi-host state synchronized from the lead \(count=\d+, "
        r"state crc ([0-9a-f]+)\)", re_,
    )
    assert lead_crcs and join_crcs
    assert join_crcs[-1] == lead_crcs[-1], (
        "rejoiner's first-tick state does not bit-match the lead's"
    )
    # one telemetry owner throughout; the lead finished the whole file
    assert _stat_lines(ro) == []
    assert _stat_lines(lo)


def test_wirecodec_dict_multihost_matches_raw_wire(tmp_path):
    """ROADMAP item 3 REMAINING: the cross-host compressed bucket rides
    the existing alignment allgather, and a two-process ``--wireCodec
    dict`` run trains IDENTICALLY (published stats byte-for-byte, final
    weights bitwise) to the raw-wire two-process run — compression is
    representation-only at fleet scale too."""
    path, _statuses = _write_replay(tmp_path, 160, seed=9)
    env = dict(os.environ, PYTHONPATH=REPO, TWTML_NOW_MS=str(NOW_MS))

    def run(codec: str, ck):
        base = _free_port_range()
        common = [
            "linear", "--source", "replay", "--replayFile", str(path),
            "--seconds", "0", "--backend", "cpu",
            "--batchBucket", "16", "--tokenBucket", "64",
            "--wire", "ragged", "--hashOn", "device",
            "--wireCodec", codec, "--checkpointDir", str(ck),
            "--lightning", CLOSED, "--twtweb", CLOSED,
        ]
        procs = [_spawn_app(i, 2, base, common, env) for i in range(2)]
        outs, errs = [], []
        for p in procs:
            o, e = p.communicate(timeout=420.0)
            if p.returncode != 0:
                pytest.fail(f"worker rc={p.returncode}:\n{e[-3000:]}")
            outs.append(o)
            errs.append(e)
        return outs, errs

    raw, _raw_errs = run("off", tmp_path / "ck_raw")
    codec, codec_errs = run("dict", tmp_path / "ck_dict")
    # the codec arm must actually COMPRESS (synthetic tweets are ASCII):
    # a silent raw fallback would make this differential vacuous
    for e in codec_errs:
        assert "shipped RAW" not in e, e[-2000:]
    assert _stat_lines(raw[1]) == _stat_lines(codec[1]) == []
    assert _stat_lines(raw[0]) == _stat_lines(codec[0])
    assert len(_stat_lines(raw[0])) >= 4

    from twtml_tpu.checkpoint import Checkpointer

    w_raw, m_raw = Checkpointer(str(tmp_path / "ck_raw")).restore()
    w_dict, m_dict = Checkpointer(str(tmp_path / "ck_dict")).restore()
    assert m_raw["count"] == m_dict["count"] == 160
    np.testing.assert_array_equal(np.asarray(w_raw), np.asarray(w_dict))


def test_tenant_fleet_two_process_matches_single_process(tmp_path):
    """PR 7 REMAINING b: ``--tenants M`` + ``--coordinator`` now runs —
    per-host sharded intake into the stacked tenant wire, ONE pooled
    fetch per tick — and the two-process fleet's published stats and
    final stacked weights match a single-process tenant run of the same
    app over the same replay."""
    path, _statuses = _write_replay(tmp_path, 128, seed=11)
    env = dict(os.environ, PYTHONPATH=REPO, TWTML_NOW_MS=str(NOW_MS))
    common = [
        "linear", "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu", "--tenants", "2",
        "--wire", "padded", "--tokenBucket", "64",
        "--lightning", CLOSED, "--twtweb", CLOSED,
    ]

    def run(nprocs, ndev, bucket, ck):
        base = _free_port_range()
        args = common + ["--batchBucket", bucket, "--checkpointDir", str(ck)]
        procs = [
            subprocess.Popen(
                [sys.executable, APP_WORKER, str(i), str(nprocs), str(base),
                 str(ndev)] + args,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=env,
            )
            for i in range(nprocs)
        ]
        outs = []
        for p in procs:
            o, e = p.communicate(timeout=420.0)
            if p.returncode != 0:
                pytest.fail(f"worker rc={p.returncode}:\n{e[-3000:]}")
            outs.append(o)
        return outs

    single = run(1, 4, "32", tmp_path / "ck1")
    multi = run(2, 2, "16", tmp_path / "ck2")
    lead, follower = _stat_lines(multi[0]), _stat_lines(multi[1])
    ref = _stat_lines(single[0])
    assert follower == []
    assert len(lead) == len(ref) >= 3
    for got, want in zip(lead, ref):
        g = [int(x) for x in re.findall(r"-?\d+", got)]
        w = [int(x) for x in re.findall(r"-?\d+", want)]
        assert g[:2] == w[:2]  # cumulative count and batch size: exact
        for a, b in zip(g[2:], w[2:]):
            assert abs(a - b) <= 2, (got, want)

    from twtml_tpu.checkpoint import Checkpointer

    w_single, m_s = Checkpointer(str(tmp_path / "ck1")).restore()
    w_multi, m_m = Checkpointer(str(tmp_path / "ck2")).restore()
    assert m_s["count"] == m_m["count"] == 128
    assert np.asarray(w_single).shape == np.asarray(w_multi).shape  # [M, F+4]
    np.testing.assert_allclose(
        np.asarray(w_multi), np.asarray(w_single), rtol=1e-4, atol=1e-7,
    )


@pytest.mark.slow
def test_healthy_eight_host_fleet_adds_no_collectives_and_no_fetches():
    """The zero-new-collectives law AT SCALE (ISSUE 17): an 8-process
    lockstep fleet with the membership plane active — 8 hosts' membership
    columns widen the one cadence allgather's payload, never its call
    count, and the pooled stats fetch stays one device_get per batch."""
    nprocs = 8
    base = _free_port_range()
    env = dict(os.environ, PYTHONPATH=REPO)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), str(nprocs), str(base), "unit",
             "elastic_count"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=420.0)
            if p.returncode != 0:
                pytest.fail(
                    f"worker failed rc={p.returncode}:\n{stderr[-3000:]}"
                )
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            p.kill()
    for o in outs:
        assert o["terminated"] and not o["failed"]
        assert o["batches"] >= 2  # 192 rows / 8 hosts = 24 each, bucket 16
        assert o["allgathers"] == o["ticks"], o
        assert o["device_gets"] == o["batches"] == o["fetch_count"], o
        assert o["epoch"] == 0 and o["members"] == list(range(nprocs))
        assert o["transitions"] == []


@pytest.mark.slow
def test_churn_storm_eight_hosts_survives_follower_and_lead_kills(tmp_path):
    """THE churn acceptance (ISSUE 17): an 8-host virtual fleet under the
    storm driver (tools/chaos_fleet.py) — a follower dies, the fleet
    shrinks; the LEAD dies, uid 1 wins the election and re-forms; a pause
    stalls a third host under the watchdog threshold (no transition). All
    epochs form, every survivor's per-reform resync CRC matches fleet-wide
    (bit-matching continuations), losses are counted, and no host aborts."""
    from tools.chaos_fleet import run_storm

    res = run_storm(
        hosts=8, tweets=1024, workdir=str(tmp_path),
        chaos=(
            "peer.kill:uid=5:tick=2,peer.kill:uid=0:tick=6,"
            "peer.pause:uid=3:ticks=1@4"
        ),
    )
    assert res["ok"], res["failures"]
    assert sorted(res["killed"]) == [0, 5]
    # one election, won by the lowest live uid of the committed view
    assert res["elections"] == 1
    assert res["winners"] == [1]
    # the fleet walked the full epoch ladder: the initial 8, then 7
    # (uid 5 dead), then 7 without uid 0 but with the elected lead (uid 1)
    assert [m for _e, m in res["epochs"]] == [
        list(range(8)), [0, 1, 2, 3, 4, 6, 7], [1, 2, 3, 4, 6, 7],
    ]
    # every reform's resync CRC agreed across every member that logged it
    assert res["crc_rounds"] and all(
        len(set(crcs)) == 1 for crcs in res["crc_rounds"]
    )
    # the sub-threshold pause caused churn, not a transition
    assert res["pauses"] >= 1 and len(res["epochs"]) == 3
