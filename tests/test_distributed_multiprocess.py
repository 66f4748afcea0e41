"""Multi-host integration: a REAL two-process jax.distributed group.

The reference's multi-node story is Spark cluster managers (README.md:40-55);
ours is jax.distributed + mesh collectives (parallel/distributed.py). The
other parallel tests exercise the program structure on a single-process
virtual mesh; this one actually forms a two-process group over localhost
(gloo CPU collectives, 2 virtual devices per process = 4 global), shards the
stream by host, assembles the global batch with host_local_batch_to_global,
and checks both processes train in lockstep — and match a single-process run
over the same tweets, for both wire formats (host-hashed tokens and raw
code units).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "distributed_worker.py")
APP_WORKER = os.path.join(REPO, "tests", "app_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_group(
    wire: str, nprocs: int = 2, timeout: float = 180.0, mesh: str = "1d",
    extra_env: dict | None = None,
):
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=REPO, **(extra_env or {}))
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), str(nprocs), str(port), wire, mesh],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=timeout)
            if p.returncode != 0:
                pytest.fail(f"worker failed rc={p.returncode}:\n{stderr[-2000:]}")
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            p.kill()
    return outs


def _single_process_expectation(wire: str):
    """The same 64 tweets, host-sharded the same way, in one process."""
    from twtml_tpu.features.batch import FeatureBatch, UnitBatch
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = list(
        SyntheticSource(total=64, seed=7, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000)
    shards = []
    for pid in range(2):
        local = statuses[pid::2]
        if wire == "unit":
            shards.append(feat.featurize_batch_units(
                local, row_bucket=16, unit_bucket=64, pre_filtered=True
            ))
        else:
            shards.append(feat.featurize_batch(
                local, row_bucket=16, token_bucket=64, pre_filtered=True
            ))
    cls = UnitBatch if wire == "unit" else FeatureBatch
    global_batch = cls(*(
        np.concatenate([getattr(s, f) for s in shards], axis=0)
        for f in cls._fields
    ))
    model = StreamingLinearRegressionWithSGD(num_iterations=5, step_size=0.005)
    out = model.step(global_batch)
    return float(out.count), float(out.mse), model.latest_weights


@pytest.mark.parametrize("wire", ["host", "unit"])
def test_two_process_group_trains_in_lockstep(wire):
    outs = _run_group(wire)
    assert [o["process"] for o in sorted(outs, key=lambda o: o["process"])] == [0, 1]
    # both processes observe identical global stats and weights
    assert outs[0]["count"] == outs[1]["count"] == 64.0
    assert outs[0]["mse"] == pytest.approx(outs[1]["mse"], rel=1e-6)
    np.testing.assert_allclose(outs[0]["weights"], outs[1]["weights"], rtol=1e-6)
    # and they match the single-process ground truth over the same tweets
    count, mse, weights = _single_process_expectation(wire)
    assert outs[0]["count"] == count
    assert outs[0]["mse"] == pytest.approx(mse, rel=1e-4)
    np.testing.assert_allclose(
        outs[0]["weights"], weights, rtol=1e-4, atol=1e-7
    )


def _run_app_group(app_args: list, nprocs: int, ndev: int, timeout=300.0,
                   extra_env: dict | None = None):
    """Drive a real entry-point main() in ``nprocs`` processes via
    tests/app_worker.py; returns each process's stdout."""
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=REPO, **(extra_env or {}))
    procs = [
        subprocess.Popen(
            [sys.executable, APP_WORKER, str(i), str(nprocs), str(port),
             str(ndev)] + app_args,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=timeout)
            if p.returncode != 0:
                pytest.fail(
                    f"app worker failed rc={p.returncode}:\n{stderr[-3000:]}"
                )
            outs.append(stdout)
    finally:
        for p in procs:
            p.kill()
    return outs


def test_app_level_multihost_cli_trains_in_lockstep(tmp_path):
    """VERDICT r2 #1 done-criterion: two processes running the REAL
    linear-regression main with ``--master twtml://host:port`` (=
    --coordinator/--numProcesses/--processId) train in lockstep — same
    batch boundaries, same global per-batch stats (±1 on the rounded ints),
    and final weights matching a single-process run of the same app over
    the same replay file on the same total device count."""
    import json as _json

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=200, seed=5, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"  # closed port: telemetry Try paths, no DNS
    common = [
        "linear", "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu", "--tokenBucket", "64",
        "--lightning", closed, "--twtweb", closed,
    ]
    d_single, d_multi = str(tmp_path / "ck1"), str(tmp_path / "ck2")
    single = _run_app_group(
        common + ["--batchBucket", "32", "--checkpointDir", d_single],
        nprocs=1, ndev=4,
    )
    multi = _run_app_group(
        common + ["--batchBucket", "16", "--checkpointDir", d_multi],
        nprocs=2, ndev=2,
    )

    def stat_lines(out):
        return [ln for ln in out.splitlines() if ln.startswith("count:")]

    import re

    lead, follower = stat_lines(multi[0]), stat_lines(multi[1])
    ref = stat_lines(single[0])
    assert follower == []  # one telemetry owner per run
    assert len(lead) == len(ref) >= 5  # same batch boundaries incl. tail

    for got, want in zip(lead, ref):
        g = [int(x) for x in re.findall(r"-?\d+", got)]
        w = [int(x) for x in re.findall(r"-?\d+", want)]
        assert g[:2] == w[:2]  # cumulative count and batch size: exact
        for a, b in zip(g[2:], w[2:]):  # mse/stdevs: rounded ints, FP order
            assert abs(a - b) <= 2, (got, want)

    from twtml_tpu.checkpoint import Checkpointer

    w_single, meta_s = Checkpointer(d_single).restore()
    w_multi, meta_m = Checkpointer(d_multi).restore()
    assert meta_s["count"] == meta_m["count"] == 200
    assert meta_s["batches"] == meta_m["batches"] == len(ref)
    np.testing.assert_allclose(w_multi, w_single, rtol=1e-4, atol=1e-7)

    # resume: a second multi-host run on the same dir is an r21 EXACT
    # resume — every host restores the lead's broadcast checkpoint and
    # fast-forwards past its own journaled shard (the corpus is fully
    # covered), so nothing retrains and the counters are unchanged
    multi2 = _run_app_group(
        common + ["--batchBucket", "16", "--checkpointDir", d_multi],
        nprocs=2, ndev=2,
    )
    assert stat_lines(multi2[0]) == []  # no new batches: exactly-once
    _, meta_m2 = Checkpointer(d_multi).restore()
    assert meta_m2["count"] == 200

    # --journal off restores the pre-r21 resume semantics: the corpus
    # re-trains on top of the restored counters on every host
    multi3 = _run_app_group(
        common + ["--batchBucket", "16", "--checkpointDir", d_multi,
                  "--journal", "off"],
        nprocs=2, ndev=2,
    )
    lead3 = stat_lines(multi3[0])
    assert lead3, "journal-off resume produced no batches"
    first = [int(x) for x in re.findall(r"-?\d+", lead3[0])]
    assert first[0] == 200 + first[1]  # cumulative count resumed from 200
    _, meta_m3 = Checkpointer(d_multi).restore()
    assert meta_m3["count"] == 400


def test_app_level_multihost_ragged_wire(tmp_path):
    """r4 (VERDICT r3 #2): the RAGGED wire through the real multi-host CLI —
    each host re-lays its rows into shard-aligned segments with the
    per-shard bucket agreed by allgather (parallel/distributed.py), and the
    run matches a single-process MESH run of the same app with the same
    wire (which itself bit-matches the padded wire,
    tests/test_ragged_sharded.py)."""
    import json as _json
    import re

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=128, seed=9, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"
    common = [
        "linear", "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu", "--tokenBucket", "64",
        "--wire", "ragged", "--hashOn", "device",
        "--lightning", closed, "--twtweb", closed,
    ]
    d_single, d_multi = str(tmp_path / "ck1"), str(tmp_path / "ck2")
    single = _run_app_group(
        common + ["--batchBucket", "32", "--checkpointDir", d_single],
        nprocs=1, ndev=4,
    )
    multi = _run_app_group(
        common + ["--batchBucket", "16", "--checkpointDir", d_multi],
        nprocs=2, ndev=2,
    )

    def stat_lines(out):
        return [ln for ln in out.splitlines() if ln.startswith("count:")]

    lead, follower = stat_lines(multi[0]), stat_lines(multi[1])
    ref = stat_lines(single[0])
    assert follower == []  # one telemetry owner per run
    assert len(lead) == len(ref) >= 3

    for got, want in zip(lead, ref):
        g = [int(x) for x in re.findall(r"-?\d+", got)]
        w = [int(x) for x in re.findall(r"-?\d+", want)]
        assert g[:2] == w[:2]  # cumulative count and batch size: exact
        for a, b in zip(g[2:], w[2:]):  # mse/stdevs: rounded ints, FP order
            assert abs(a - b) <= 2, (got, want)

    from twtml_tpu.checkpoint import Checkpointer

    w_single, meta_s = Checkpointer(d_single).restore()
    w_multi, meta_m = Checkpointer(d_multi).restore()
    assert meta_s["count"] == meta_m["count"] == 128
    np.testing.assert_allclose(w_multi, w_single, rtol=1e-4, atol=1e-7)

    # the one-data-shard-per-process topology (local_shards == 1): a flat
    # batch is trivially "aligned" and hosts' buffers can differ — the
    # agreed bucket must grow the smaller host, never raise (r4 review)
    d_one = str(tmp_path / "ck3")
    one = _run_app_group(
        common + ["--batchBucket", "16", "--checkpointDir", d_one],
        nprocs=2, ndev=1,
    )
    lead1 = stat_lines(one[0])
    assert stat_lines(one[1]) == []
    assert len(lead1) == len(ref)
    w_one, meta_o = Checkpointer(d_one).restore()
    assert meta_o["count"] == 128
    np.testing.assert_allclose(w_one, w_single, rtol=1e-4, atol=1e-7)


def test_app_level_multihost_kmeans_lockstep(tmp_path):
    """The k-means entry through the multi-host CLI: per-host sharded
    intake, GLOBAL per-batch StandardScaler, mesh psums spanning hosts —
    lead-printed centers/counts match a single-process run of the same app
    over the same replay file (same global batch rows, interleaved
    order)."""
    import json as _json
    import re

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=96, seed=6, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"
    common = [
        "kmeans", "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--lightning", closed, "--twtweb", closed,
    ]
    single = _run_app_group(common + ["--batchBucket", "32"], nprocs=1, ndev=4)
    multi = _run_app_group(common + ["--batchBucket", "16"], nprocs=2, ndev=2)

    def stat_lines(out):
        return [ln for ln in out.splitlines() if ln.startswith("count:")]

    lead, follower = stat_lines(multi[0]), stat_lines(multi[1])
    ref = stat_lines(single[0])
    assert follower == []
    assert len(lead) == len(ref) >= 2
    for got, want in zip(lead, ref):
        g = [float(x) for x in re.findall(r"-?\d+\.?\d*", got)]
        w = [float(x) for x in re.findall(r"-?\d+\.?\d*", want)]
        assert g[:2] == w[:2]  # cumulative count and batch size: exact
        # centers (rounded to 3 decimals) agree within FP-order noise of
        # the interleaved global row order
        assert len(g) == len(w)
        for a, b in zip(g[2:], w[2:]):
            assert abs(a - b) <= max(0.02, 0.02 * abs(b)), (got, want)


def test_two_process_2d_mesh_checkpoint_roundtrip(tmp_path):
    """Checkpoint round-trip where weight shards span PROCESS boundaries:
    latest_weights process_allgathers, pid 0 writes, both restore into fresh
    models whose text shards are not fully addressable, training continues —
    equal to an uninterrupted 2-step single-process run."""
    outs = _run_group(
        "unit", mesh="2d_ckpt", extra_env={"TWTML_CKPT_DIR": str(tmp_path)}
    )
    assert outs[0]["count"] == outs[1]["count"] == 64.0
    np.testing.assert_allclose(outs[0]["weights"], outs[1]["weights"], rtol=1e-6)

    # single-process ground truth: the same two steps, no interruption
    from twtml_tpu.models import StreamingLinearRegressionWithSGD

    from twtml_tpu.features.batch import UnitBatch
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.sources import SyntheticSource

    statuses = list(
        SyntheticSource(total=64, seed=7, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000)
    shards = [
        feat.featurize_batch_units(
            statuses[pid::2], row_bucket=16, unit_bucket=64, pre_filtered=True
        )
        for pid in range(2)
    ]
    global_batch = UnitBatch(*(
        np.concatenate([getattr(s, f) for s in shards], axis=0)
        for f in UnitBatch._fields
    ))
    model = StreamingLinearRegressionWithSGD(num_iterations=5, step_size=0.005)
    model.step(global_batch)
    model.step(global_batch)
    np.testing.assert_allclose(
        outs[0]["weights"], model.latest_weights, rtol=1e-4, atol=1e-7
    )


def test_two_process_2d_mesh_feature_sharding():
    """(data=2, model=2) mesh across TWO processes with the model axis
    deliberately pairing devices from DIFFERENT processes: the per-iteration
    feature-shard psum crosses the process boundary (the DCN-analog path),
    each weight shard is not fully addressable from one process (the
    latest_weights allgather), and the result still matches the
    single-process ground truth."""
    outs = _run_group("unit", mesh="2d")
    assert outs[0]["count"] == outs[1]["count"] == 64.0
    np.testing.assert_allclose(outs[0]["weights"], outs[1]["weights"], rtol=1e-6)
    count, mse, weights = _single_process_expectation("unit")
    assert outs[0]["mse"] == pytest.approx(mse, rel=1e-4)
    np.testing.assert_allclose(outs[0]["weights"], weights, rtol=1e-4, atol=1e-7)


def test_two_process_2d_mesh_gram_inner_loop():
    """The Gram (dual) inner loop with both of its per-batch collectives
    crossing REAL process boundaries — the batch all-gather over 'data' and
    the G row-panel psum over 'model' (models/sgd.py run_dual_loop,
    parallel/sharding.py) — still matches the single-process dense math."""
    outs = _run_group("unit", mesh="2d_gram")
    assert outs[0]["count"] == outs[1]["count"] == 64.0
    np.testing.assert_allclose(outs[0]["weights"], outs[1]["weights"], rtol=1e-6)
    _, mse, weights = _single_process_expectation("unit")
    assert outs[0]["mse"] == pytest.approx(mse, rel=1e-4)
    np.testing.assert_allclose(outs[0]["weights"], weights, rtol=1e-4, atol=1e-6)


def test_two_process_tenants_on_cross_process_model_axis():
    """ISSUE 7: the multi-tenant plane with the TENANT axis on the
    cross-process MODEL axis — each process holds half the tenants' weight
    shards (not fully addressable → the latest_weights allgather runs),
    rows shard over 'data', and no collective crosses the tenant axis.
    Both processes must agree exactly with each other AND match a
    single-process tenant stack over the same stream."""
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.parallel import TenantStackModel
    from twtml_tpu.streaming.sources import SyntheticSource

    outs = _run_group("unit", mesh="tenants")
    assert outs[0]["weights_addressable"] is False
    # cross-host agreement is exact: same program, same placement
    assert outs[0]["tenant_counts"] == outs[1]["tenant_counts"]
    assert outs[0]["tenant_mses"] == outs[1]["tenant_mses"]
    np.testing.assert_array_equal(outs[0]["weights"], outs[1]["weights"])

    statuses = list(
        SyntheticSource(total=64, seed=7, base_ms=1785320000000).produce()
    )
    feat = Featurizer(now_ms=1785320000000)
    ref = TenantStackModel(4, num_iterations=5, step_size=0.005)
    for sts in (statuses[:32], statuses[32:]):
        out = ref.step(feat.featurize_batch_units(
            sts, row_bucket=32, unit_bucket=64, pre_filtered=True
        ))
    assert outs[0]["tenant_counts"] == np.asarray(out.count).tolist()
    np.testing.assert_allclose(
        outs[0]["tenant_mses"], np.asarray(out.mse).tolist(), rtol=1e-5
    )
    np.testing.assert_allclose(
        outs[0]["weights"], ref.latest_weights, rtol=1e-4, atol=1e-7
    )


def test_app_level_multihost_sentinel_rollback(tmp_path):
    """r7 (ISSUE 4): the divergence sentinel on a REAL two-process group.
    Each host's --chaos source.nan@2 poisons its local rows of the SAME
    global batch (per-host injectors, identical tick counters), both hosts
    see the same non-finite psum stats at the same deterministic delivery,
    and both roll back the same step: the lead restores its verified
    checkpoint from disk and BROADCASTS it (the follower has no checkpoint
    files), the rollback count rides the cadence allgather with no
    disagreement abort, the poisoned batch is skipped, and the run
    completes cleanly."""
    import json as _json

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=96, seed=33, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"
    d_ck = str(tmp_path / "ck")
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=REPO)
    procs = [
        subprocess.Popen(
            [sys.executable, APP_WORKER, str(i), "2", str(port), "2",
             "linear", "--source", "replay", "--replayFile", str(path),
             "--seconds", "0", "--backend", "cpu",
             "--batchBucket", "16", "--tokenBucket", "64",
             "--checkpointDir", d_ck, "--checkpointEvery", "1",
             "--chaos", "source.nan@2",
             "--lightning", closed, "--twtweb", closed],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs, errs = [], []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=300.0)
            if p.returncode != 0:
                pytest.fail(
                    f"worker failed rc={p.returncode}:\n{stderr[-3000:]}"
                )
            outs.append(stdout)
            errs.append(stderr)
    finally:
        for p in procs:
            p.kill()

    # BOTH hosts rolled back (lead from disk, follower via the broadcast)
    # — and the allgather-ridden counts never disagreed
    for err in errs:
        assert "rolled back to verified checkpoint" in err, err[-2000:]
        assert "disagree on sentinel rollback counts" not in err

    lead = [ln for ln in outs[0].splitlines() if ln.startswith("count:")]
    follower = [ln for ln in outs[1].splitlines() if ln.startswith("count:")]
    assert follower == []  # one telemetry owner per run
    # 3 global batches of 32; the sentinel skips the poisoned 2nd, and the
    # r21 intake journal (auto-on with --checkpointDir) replays its rows
    # on BOTH hosts — the journal seam sits upstream of the poison
    # injection point, so they re-featurize clean and all 3 batches train
    assert len(lead) == 3
    assert "count: 96" in lead[-1]
    for err in errs:
        assert "journal: replayed" in err, err[-2000:]

    from twtml_tpu.checkpoint import Checkpointer

    state, meta = Checkpointer(d_ck).restore()
    assert meta["count"] == 96
    assert meta["batches"] == 3
    assert np.isfinite(np.asarray(state)).all()


def test_sideband_straggler_names_delayed_host_with_no_extra_collectives():
    """ISSUE 5 acceptance: a REAL two-process lockstep run with host 1
    artificially delayed via --chaos (a step:delay stall inside the
    dispatch window). The per-host sideband rides the one cadence
    allgather — asserted by COUNTING the allgathers (exactly one per
    lockstep tick: the cadence count is unchanged by the sideband) and the
    jax.device_get calls (one per dispatched batch: zero added host
    fetches) — and BOTH hosts' straggler attributors must name host 1,
    attributed to the upload (dispatch) rung of the bottleneck ladder."""
    outs = _run_group("unit", mesh="sideband", timeout=240.0)
    by_pid = {o["process"]: o for o in outs}
    for pid in (0, 1):
        o = by_pid[pid]
        assert o["terminated"] and not o["failed"]
        assert o["batches"] >= 6
        # zero added collectives: the cadence allgather count IS the tick
        # count — the sideband widened the payload, never the call count
        assert o["allgathers"] == o["ticks"], o
        # zero added host fetches: one pooled device_get per dispatched
        # batch (the FetchPipeline contract), none from the sideband
        assert o["device_gets"] == o["batches"] == o["fetch_count"], o
        # every host sees the whole fleet and the same verdict
        assert o["num_hosts_seen"] == 2
        assert o["straggler_host"] == 1, o
        assert o["view_straggler"] == 1
        assert o["view_stage"] == "upload", o
        assert o["tick_skew_ms"] > 50.0, o


def test_lockstep_abort_propagates_instead_of_hanging():
    """A batch failure on one host aborts the GROUP: the failing host
    broadcasts abort on its next tick, the healthy peer stops instead of
    stalling in its next collective, and both mark the run failed."""
    outs = _run_group("unit", mesh="lockstep_abort", timeout=120.0)
    by_pid = {o["process"]: o for o in outs}
    assert by_pid[0]["terminated"] and by_pid[1]["terminated"]
    assert by_pid[0]["failed"] and by_pid[1]["failed"]
    assert by_pid[1]["batches_seen"] == 3  # raised on its third batch


def test_lockstep_peer_death_watchdog_aborts_survivor():
    """A HARD-killed peer (os._exit mid-run: no abort broadcast, no
    goodbye) must not leave the survivor hanging forever in its next
    cadence allgather: the lockstep peer watchdog
    (TWTML_LOCKSTEP_TIMEOUT_S) — or the transport error a dead gloo peer
    raises — turns it into a loud failed abort within the timeout."""
    port = _free_port()
    env = dict(
        os.environ, PYTHONPATH=REPO, TWTML_LOCKSTEP_TIMEOUT_S="5",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), "2", str(port), "unit",
             "peer_kill"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    try:
        out0, err0 = procs[0].communicate(timeout=120.0)
        out1, _ = procs[1].communicate(timeout=120.0)
    finally:
        for p in procs:
            p.kill()
    assert procs[1].returncode == 42  # the hard kill
    # it never got to print its result (the installed gloo prints its own
    # "[Gloo] Rank ..." connection banner on stdout — not the worker's)
    assert [
        line for line in out1.splitlines()
        if line.strip() and not line.startswith("[Gloo]")
    ] == []
    assert procs[0].returncode == 0, f"survivor crashed:\n{err0[-3000:]}"
    res = json.loads(out0.strip().splitlines()[-1])
    assert res["terminated"], "survivor never left the lockstep loop"
    assert res["failed"], "survivor did not mark the run failed"
    assert res["batches_seen"] >= 3  # it trained up to the kill point


def test_app_level_multihost_wall_clock_intervals(tmp_path):
    """The lockstep scheduler's WALL-CLOCK branch (--seconds > 0): hosts
    tick on their own clocks, the per-tick allgather aligns them, and the
    run completes with all rows trained and one telemetry owner."""
    import json as _json

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in SyntheticSource(total=64, seed=8, base_ms=1785320000000).produce():
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"
    multi = _run_app_group([
        "linear", "--source", "replay", "--replayFile", str(path),
        "--seconds", "1", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--lightning", closed, "--twtweb", closed,
    ], nprocs=2, ndev=2)

    lead = [ln for ln in multi[0].splitlines() if ln.startswith("count:")]
    follower = [ln for ln in multi[1].splitlines() if ln.startswith("count:")]
    assert follower == []
    assert lead, "no stats lines from the lead"
    assert "count: 64" in lead[-1]  # every row trained, wall-clock cadence


def test_app_level_multihost_block_ingest(tmp_path):
    """r5 (VERDICT r4 #4): --ingest block on a two-process group — each
    host parses only its BYTE-RANGE shard of the replay file
    (BlockReplayFileSource shard_index/count), lockstep drains split
    blocks to exactly the pinned bucket, and the run matches an in-process
    ground truth that emulates the same per-host intake (concatenated
    per-host buckets per tick through one single-device model)."""
    import json as _json

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=200, seed=21, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"
    d_multi = str(tmp_path / "ck")
    multi = _run_app_group([
        "linear", "--source", "replay", "--replayFile", str(path),
        "--ingest", "block", "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--checkpointDir", d_multi,
        "--lightning", closed, "--twtweb", closed,
    ], nprocs=2, ndev=1,
        # pin the age-feature clock so the in-process ground truth below
        # (same fixed clock) is comparable bit-for-bit in features
        extra_env={"TWTML_NOW_MS": "1785320000000"})

    lead = [ln for ln in multi[0].splitlines() if ln.startswith("count:")]
    follower = [ln for ln in multi[1].splitlines() if ln.startswith("count:")]
    assert follower == []
    assert lead, "no stats lines from the lead"

    # in-process ground truth: the same byte-range shards, the same
    # 16-row buckets per tick, concatenated host0+host1 into the global
    # batch, through one single-device model
    from twtml_tpu.features.batch import UnitBatch
    from twtml_tpu.features.blocks import iter_row_chunks, empty_block
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.streaming.sources import BlockReplayFileSource

    feat = Featurizer(now_ms=1785320000000)
    chunks = [
        list(iter_row_chunks(
            BlockReplayFileSource(
                str(path), shard_index=i, shard_count=2
            ).produce(), 16,
        ))
        for i in range(2)
    ]
    ticks = max(len(c) for c in chunks)
    # conf defaults (reference.conf): numIterations 50, stepSize 0.005
    model = StreamingLinearRegressionWithSGD(num_iterations=50, step_size=0.005)
    total = 0
    for k in range(ticks):
        host_batches = [
            feat.featurize_parsed_block(
                c[k] if k < len(c) else empty_block(),
                row_bucket=16, unit_bucket=64,
            )
            for c in chunks
        ]
        global_batch = UnitBatch(*(
            np.concatenate([getattr(b, f) for b in host_batches], axis=0)
            for f in UnitBatch._fields
        ))
        out = model.step(global_batch)
        total += int(out.count)
    assert total == 200

    from twtml_tpu.checkpoint import Checkpointer

    w_multi, meta = Checkpointer(d_multi).restore()
    assert meta["count"] == 200
    assert len(lead) == ticks
    np.testing.assert_allclose(
        w_multi, model.latest_weights, rtol=1e-4, atol=1e-7
    )


def test_app_level_multihost_checkpoint_cadence_drains(tmp_path):
    """--checkpointEvery on a multi-host group: the fetch pipeline runs
    DETERMINISTIC there (emits only at counter-driven points), so the
    cadence drains land on the same tick on both hosts — the run neither
    hangs in a collective nor changes a single stat line against the same
    two-process run without mid-stream saves, and the mid-stream
    checkpoints exist."""
    import glob
    import json as _json

    from twtml_tpu.streaming.sources import SyntheticSource

    path = tmp_path / "tweets.jsonl"
    statuses = list(
        SyntheticSource(total=160, seed=23, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(_json.dumps(s.to_json()) + "\n")

    closed = "http://127.0.0.1:9"
    common = [
        "linear", "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--lightning", closed, "--twtweb", closed,
    ]
    d_plain, d_cadence = str(tmp_path / "ck1"), str(tmp_path / "ck2")
    plain = _run_app_group(
        common + ["--checkpointDir", d_plain], nprocs=2, ndev=2
    )
    cadence = _run_app_group(
        common + ["--checkpointDir", d_cadence, "--checkpointEvery", "2"],
        nprocs=2, ndev=2,
    )

    def stat_lines(out):
        return [ln for ln in out.splitlines() if ln.startswith("count:")]

    assert stat_lines(cadence[1]) == []  # one telemetry owner per run
    assert stat_lines(cadence[0]) == stat_lines(plain[0])
    assert len(stat_lines(plain[0])) >= 5

    from twtml_tpu.checkpoint import Checkpointer

    w_plain, meta_p = Checkpointer(d_plain).restore()
    w_cadence, meta_c = Checkpointer(d_cadence).restore()
    assert meta_p["count"] == meta_c["count"] == 160
    np.testing.assert_allclose(w_cadence, w_plain, rtol=1e-6, atol=1e-8)
    # saves happened mid-stream, not only at shutdown
    assert len(glob.glob(os.path.join(d_cadence, "ckpt-*.npz"))) > len(
        glob.glob(os.path.join(d_plain, "ckpt-*.npz"))
    )
