"""Serving plane (ISSUE 9): parity, promotion gate, coalescer, hot-swap,
chaos, and the HTTP front door.

The read-path parity law: serve-path predictions must BIT-equal the fused
train step's reported predictions for the same snapshot and batch — the
train step predicts with PRE-update weights (predict-then-train,
LinearRegression.scala:85-86), and the predict-only program is that same
traced prologue with a zero-iteration loop (serving/engine.py). Every test
here runs the REAL plane (threads, FetchPipeline, watchdog) on the CPU
backend.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from twtml_tpu.config import ConfArguments  # noqa: E402
from twtml_tpu.features.featurizer import Featurizer  # noqa: E402
from twtml_tpu.models import (  # noqa: E402
    StreamingLinearRegressionWithSGD,
)
from twtml_tpu.serving import (  # noqa: E402
    ServingClient,
    ServingSnapshot,
    SnapshotPromoter,
    is_promotable,
    load_servable,
)
from twtml_tpu.serving.plane import ServingPlane  # noqa: E402
from twtml_tpu.streaming import faults  # noqa: E402
from twtml_tpu.streaming.sources import SyntheticSource  # noqa: E402
from twtml_tpu.telemetry import metrics as _metrics  # noqa: E402

NOW_MS = 1785320000000
CLOSED = "http://127.0.0.1:9"  # closed port: telemetry best-effort no-ops


@pytest.fixture(autouse=True)
def _clean():
    _metrics.reset_for_tests()
    faults.uninstall_chaos()
    yield
    faults.uninstall_chaos()
    _metrics.reset_for_tests()


def _statuses(n, seed=3):
    return list(SyntheticSource(total=n, seed=seed).produce())


def _feat():
    return Featurizer(now_ms=NOW_MS)


def _trained_weights(n=32, steps=1):
    """Non-trivial single-model weights from a short real training run."""
    import jax

    feat = _feat()
    model = StreamingLinearRegressionWithSGD()
    statuses = _statuses(n * steps, seed=11)
    for k in range(steps):
        b = feat.featurize_batch_ragged(
            statuses[k * n:(k + 1) * n], row_bucket=n, pre_filtered=True
        )
        jax.device_get(model.step(b))
    return model.latest_weights.copy()


def _plane(snapshot, **kw):
    kw.setdefault("featurizer", _feat())
    kw.setdefault("batch_rows", 32)
    kw.setdefault("max_wait_ms", 5.0)
    kw.setdefault("depth", 4)
    return ServingPlane(snapshot, **kw)


# ---------------------------------------------------------------------------
# the promotion predicate + gate tool

def test_is_promotable_predicate():
    ok, _ = is_promotable({"finite": True, "quality": {"level": "ok"}})
    assert ok
    ok, _ = is_promotable({"finite": True, "quality": {"level": "warn"}})
    assert ok  # warn serves
    ok, reason = is_promotable(
        {"finite": True, "quality": {"level": "alert", "drift_score": 9.0}}
    )
    assert not ok and "alert" in reason  # alert refuses
    ok, reason = is_promotable({"finite": False})
    assert not ok and "finite" in reason
    ok, reason = is_promotable({"finite": True})  # unstamped serves
    assert ok and "unstamped" in reason
    ok, _ = is_promotable(None)
    assert not ok


def _save_ckpt(directory, step, weights, level=None, finite_weights=True):
    from twtml_tpu.checkpoint import Checkpointer

    meta = {"count": step * 10, "batches": step}
    if level is not None:
        meta["quality"] = {"level": level, "drift_score": 5.0,
                           "loss_trend": 0.1}
    w = np.asarray(weights, np.float32)
    if not finite_weights:
        w = w.copy()
        w[0] = np.nan
    return Checkpointer(str(directory)).save(step, w, meta)


def test_model_report_gate_exit_codes(tmp_path):
    """--gate: 0 promotable, 1 not promotable, 2 malformed — running the
    serving plane's own predicate (the ops/server agreement law)."""
    from tools.model_report import main as report_main

    w = np.arange(1004, dtype=np.float32)
    ok_dir = tmp_path / "ok"
    _save_ckpt(ok_dir, 1, w, level="warn")
    assert report_main([str(ok_dir), "--gate"]) == 0

    alert_dir = tmp_path / "alert"
    _save_ckpt(alert_dir, 1, w, level="alert")
    assert report_main([str(alert_dir), "--gate"]) == 1

    # quarantined-only directory: archives exist but none is servable
    quar_dir = tmp_path / "quar"
    _save_ckpt(quar_dir, 1, w, level="ok", finite_weights=False)
    assert report_main([str(quar_dir), "--gate"]) == 1

    assert report_main([str(tmp_path / "missing"), "--gate"]) == 2

    # the gate's verdict IS load_servable's (one predicate, two faces)
    snap, _ = load_servable(str(alert_dir))
    assert snap is None
    snap, _ = load_servable(str(ok_dir))
    assert snap is not None and snap.step == 1 and snap.num_tenants == 1


def test_model_report_gate_json(tmp_path, capsys):
    from tools.model_report import main as report_main

    _save_ckpt(tmp_path / "d", 7, np.zeros(1004, np.float32), level="ok")
    assert report_main([str(tmp_path / "d"), "--gate", "--json"]) == 0
    verdict = json.loads(capsys.readouterr().out.strip())
    assert verdict["promotable"] is True and verdict["step"] == 7


# ---------------------------------------------------------------------------
# read-path parity: serve predictions BIT-equal the train step's

def test_serve_predictions_bit_equal_train_step():
    """THE parity law on the read path: for the same snapshot and batch,
    the plane's predictions are bitwise the fused train step's pre-update
    predictions (predict-then-train ordering + HALF_UP rounding included —
    it is literally the same traced prologue)."""
    import jax

    w = _trained_weights()
    statuses = _statuses(24, seed=5)
    snap = ServingSnapshot(step=3, weights=w,
                           meta={"quality": {"level": "ok"}})
    plane = _plane(snap).start()
    try:
        res = plane.submit(statuses).result(timeout=120)
    finally:
        plane.stop()
    got = np.asarray(res["predictions"], np.float32)
    assert res["snapshot_step"] == 3

    # ground truth: the TRAIN step on the identical featurized batch
    batch = _feat().featurize_batch_ragged(
        statuses, row_bucket=32, pre_filtered=True
    )
    ref_model = StreamingLinearRegressionWithSGD().set_initial_weights(w)
    out = jax.device_get(ref_model.step(batch))
    ref = np.asarray(out.predictions)[np.asarray(batch.mask) > 0]
    assert np.array_equal(ref, got)

    # ...and the train step MOVED its weights (so the parity above really
    # pinned the PRE-update predictions, not a no-op model)
    assert not np.array_equal(ref_model.latest_weights, w)
    # serving never moved the snapshot
    assert np.array_equal(
        np.asarray(plane._engine.model.latest_weights), w
    )


def test_serve_predictions_bit_equal_per_tenant_models():
    """Tenant-stack parity: an [M, F+4] snapshot serves every row with the
    SAME bits its tenant's standalone single model would produce, re-ordered
    to original request rows through the deterministic route."""
    import jax

    from twtml_tpu.features.batch import tenant_route_keys

    m_tenants = 4
    rng = np.random.default_rng(0)
    stack = (rng.standard_normal((m_tenants, 1004)) * 1e-3).astype(np.float32)
    statuses = _statuses(24, seed=9)
    snap = ServingSnapshot(step=5, weights=stack,
                           meta={"quality": {"level": "ok"}})
    plane = _plane(snap).start()
    try:
        res = plane.submit(statuses).result(timeout=240)
    finally:
        plane.stop()
    got = np.asarray(res["predictions"], np.float32)
    assert got.shape == (24,)

    batch = _feat().featurize_batch_ragged(
        statuses, row_bucket=32, pre_filtered=True
    )
    route = tenant_route_keys(batch, m_tenants)
    assert len(set(route[:24].tolist())) > 1  # the split actually split
    ref = np.zeros(24, np.float32)
    for m in range(m_tenants):
        model = StreamingLinearRegressionWithSGD().set_initial_weights(
            stack[m]
        )
        out = jax.device_get(model.step(batch))
        preds = np.asarray(out.predictions)
        rows = np.nonzero(route[:24] == m)[0]
        ref[rows] = preds[rows]
    assert np.array_equal(ref, got)


# ---------------------------------------------------------------------------
# coalescer semantics

def test_coalescer_one_dispatch_for_queued_requests():
    """Requests queued together ride ONE dispatch (the whole point: one
    featurize + one device program + one fetch per coalesced batch), and
    each future gets exactly its own rows back."""
    w = np.zeros(1004, np.float32)
    snap = ServingSnapshot(step=1, weights=w)
    plane = _plane(snap, batch_rows=64, max_wait_ms=20.0)
    steps = []
    real_step = plane._engine.model.step

    def counting_step(wire):
        steps.append(1)
        return real_step(wire)

    plane._engine.model.step = counting_step
    futs = [plane.submit(_statuses(8, seed=s)) for s in range(4)]
    plane.start()  # queued BEFORE the loop runs → one group, one dispatch
    try:
        results = [f.result(timeout=120) for f in futs]
    finally:
        plane.stop()
    assert len(steps) == 1
    assert all(len(r["predictions"]) == 8 for r in results)
    assert _metrics.get_registry().counter("serve.batches").snapshot() == 1
    assert _metrics.get_registry().counter("serve.requests").snapshot() == 4


def test_partial_batch_dispatches_after_bounded_wait():
    """A lone sub-bucket request must not wait for the bucket to fill —
    the --serveMaxWaitMs bound dispatches the partial batch."""
    snap = ServingSnapshot(step=1, weights=np.zeros(1004, np.float32))
    plane = _plane(snap, batch_rows=256, max_wait_ms=10.0).start()
    try:
        res = plane.submit(_statuses(4)).result(timeout=120)
    finally:
        plane.stop()
    assert len(res["predictions"]) == 4


def test_oversized_and_empty_requests():
    snap = ServingSnapshot(step=1, weights=np.zeros(1004, np.float32))
    plane = _plane(snap, batch_rows=8).start()
    try:
        with pytest.raises(ValueError, match="serveBatchRows"):
            plane.submit(_statuses(9)).result(timeout=10)
        assert plane.submit([]).result(timeout=10)["predictions"] == []
    finally:
        plane.stop()


def test_statuses_from_rows_faces():
    rows = [
        "bare text",
        {"text": "plain", "followers_count": 10, "created_at_ms": NOW_MS},
        {"text": "rt wrapper ignored", "retweeted_status": {
            "text": "original", "retweet_count": 7,
            "user": {"followers_count": 3}, "timestamp_ms": str(NOW_MS),
        }},
    ]
    statuses = ServingPlane.statuses_from_rows(rows)
    assert [s.retweeted_status.text for s in statuses] == [
        "bare text", "plain", "original",
    ]
    assert statuses[1].retweeted_status.followers_count == 10
    assert statuses[1].retweeted_status.created_at_ms == NOW_MS
    assert statuses[2].retweeted_status.retweet_count == 7
    with pytest.raises(ValueError):
        ServingPlane.statuses_from_rows([42])


# ---------------------------------------------------------------------------
# snapshot promotion + atomic hot-swap

def test_promoter_promotes_ok_and_refuses_alert(tmp_path):
    import jax

    ck = tmp_path / "ck"
    w1 = np.zeros(1004, np.float32)
    _save_ckpt(ck, 1, w1, level="ok")
    snap, reason = load_servable(str(ck))
    assert snap is not None and "ok" in reason
    plane = _plane(snap).start()
    promoter = SnapshotPromoter(str(ck), plane, poll_s=30.0)
    try:
        # an alert-stamped newer checkpoint is REFUSED; serving stays put
        w2 = np.full(1004, 0.5, np.float32)
        _save_ckpt(ck, 2, w2, level="alert")
        assert promoter.poll_once() is False
        assert plane.snapshot_step == 1
        assert _metrics.get_registry().counter(
            "serve.promotions_refused").snapshot() == 1

        # a healthy newer checkpoint hot-swaps in. poll_once STAGES the
        # swap; the serve loop applies it between dispatches (the atomic-
        # swap contract), so give its next tick a bounded moment to land
        w3 = np.full(1004, 0.25, np.float32)
        _save_ckpt(ck, 3, w3, level="warn")
        assert promoter.poll_once() is True
        deadline = time.monotonic() + 10
        while plane.snapshot_step != 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert plane.snapshot_step == 3

        # served predictions now come from w3 (swap really landed)
        statuses = _statuses(8)
        res = plane.submit(statuses).result(timeout=120)
        assert res["snapshot_step"] == 3
        batch = _feat().featurize_batch_ragged(
            statuses, row_bucket=32, pre_filtered=True
        )
        ref_model = StreamingLinearRegressionWithSGD().set_initial_weights(w3)
        ref = np.asarray(jax.device_get(ref_model.step(batch)).predictions)[
            np.asarray(batch.mask) > 0
        ]
        assert np.array_equal(ref, np.asarray(res["predictions"], np.float32))
    finally:
        promoter.stop()
        plane.stop()


def test_hot_swap_under_load_tears_nothing():
    """Hot-swap while requests stream: every request resolves, and each
    response's predictions match EXACTLY the snapshot its reported step
    names — never a half-applied mix (the atomic-swap law)."""
    import jax

    statuses = _statuses(8, seed=21)
    batch = _feat().featurize_batch_ragged(
        statuses, row_bucket=32, pre_filtered=True
    )
    refs = {}
    w_a = np.zeros(1004, np.float32)
    w_b = (np.arange(1004) % 7).astype(np.float32) * 1e-3
    for step, w in ((1, w_a), (2, w_b)):
        model = StreamingLinearRegressionWithSGD().set_initial_weights(w)
        out = jax.device_get(model.step(batch))
        refs[step] = np.asarray(out.predictions)[
            np.asarray(batch.mask) > 0
        ]

    plane = _plane(
        ServingSnapshot(step=1, weights=w_a), max_wait_ms=0.5,
    ).start()
    plane.warmup()
    results = []
    errors = []

    def loader():
        try:
            for _ in range(10):
                results.append(
                    plane.submit(list(statuses)).result(timeout=120)
                )
        except Exception as exc:  # pragma: no cover - failure evidence
            errors.append(exc)

    threads = [threading.Thread(target=loader) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        plane.hot_swap(ServingSnapshot(step=2, weights=w_b))
        for t in threads:
            t.join(timeout=180)
    finally:
        plane.stop()
    assert not errors
    assert len(results) == 30  # zero requests lost
    seen_steps = set()
    for res in results:
        step = res["snapshot_step"]
        seen_steps.add(step)
        # the predictions must be EXACTLY the reported snapshot's — a torn
        # swap would produce a vector matching neither reference
        assert np.array_equal(
            refs[step], np.asarray(res["predictions"], np.float32)
        ), f"response torn across snapshots (claimed step {step})"
    assert 2 in seen_steps  # the swap actually served traffic


# ---------------------------------------------------------------------------
# chaos: the serve path trips the existing guards, never hangs a client

def test_chaos_fetch_error_trips_watchdog_not_client_hang(monkeypatch):
    monkeypatch.setenv("TWTML_FETCH_DEADLINE_S", "0.5")
    monkeypatch.setenv("TWTML_FETCH_RETRIES", "1")
    faults.install_chaos("fetch:error@1")
    snap = ServingSnapshot(step=1, weights=np.zeros(1004, np.float32))
    plane = _plane(snap).start()
    try:
        fut = plane.submit(_statuses(4))
        with pytest.raises(RuntimeError, match="watchdog|abort"):
            fut.result(timeout=120)
        assert plane.failed
        # the guard machinery fired: retries then a counted abort
        assert _metrics.get_registry().counter(
            "fetch.aborts").snapshot() == 1
        assert _metrics.get_registry().counter(
            "fetch.retries").snapshot() >= 1
        assert _metrics.get_registry().counter(
            "serve.errors").snapshot() >= 1
        # subsequent submits fail FAST (no queue into a dead plane)
        with pytest.raises(RuntimeError, match="aborted"):
            plane.submit(_statuses(2)).result(timeout=10)
    finally:
        faults.uninstall_chaos()
        plane.stop()


def test_idle_stalled_fetch_reissues_and_recovers(monkeypatch):
    """The idle-server wedged-fetch case: ONE stalled fetch with no
    follow-up traffic must still hit the watchdog deadline (the serve
    loop's poll path enforces it), re-issue — the arrays stay resident, so a
    duplicate device_get is safe — and the request completes instead of hanging
    until the next request arrives."""
    monkeypatch.setenv("TWTML_FETCH_DEADLINE_S", "0.3")
    monkeypatch.setenv("TWTML_FETCH_RETRIES", "3")
    import jax

    from twtml_tpu.serving.engine import PredictEngine

    engine = PredictEngine(num_text_features=1000)
    stalled = {"n": 0}

    def one_shot_stall(out):
        host = jax.device_get(out)
        stalled["n"] += 1
        if stalled["n"] == 1:  # only the FIRST fetch wedges
            time.sleep(1.2)
        return host

    engine.fetch_output = one_shot_stall
    snap = ServingSnapshot(step=1, weights=np.zeros(1004, np.float32))
    plane = _plane(snap, engine=engine).start()
    try:
        res = plane.submit(_statuses(4)).result(timeout=120)
        assert len(res["predictions"]) == 4
        assert not plane.failed
        assert _metrics.get_registry().counter(
            "fetch.retries").snapshot() >= 1
    finally:
        plane.stop()


# ---------------------------------------------------------------------------
# zero added train-path fetches + train bit-identity with serving live

def _write_replay(tmp_path, n, seed=31):
    path = tmp_path / "tweets.jsonl"
    with open(path, "w", encoding="utf-8") as fh:
        for s in SyntheticSource(total=n, seed=seed, base_ms=NOW_MS).produce():
            d = {
                "text": s.text, "retweet_count": s.retweet_count,
                "user": {"followers_count": s.followers_count,
                         "favourites_count": s.favourites_count,
                         "friends_count": s.friends_count},
                "timestamp_ms": str(s.created_at_ms), "lang": s.lang or "en",
            }
            if s.retweeted_status is not None:
                r = s.retweeted_status
                d["retweeted_status"] = {
                    "text": r.text, "retweet_count": r.retweet_count,
                    "user": {"followers_count": r.followers_count,
                             "favourites_count": r.favourites_count,
                             "friends_count": r.friends_count},
                    "timestamp_ms": str(r.created_at_ms),
                }
            fh.write(json.dumps(d) + "\n")
    return path


def test_serving_adds_zero_train_fetches_and_keeps_training_bit_identical(
    tmp_path, monkeypatch
):
    """ACCEPTANCE: with a serving plane + promoter live against the train
    run's checkpoint directory, the train path still fetches exactly once
    per batch (promotion is DISK-only), and the trained weights are
    bit-identical to a run with no serving at all."""
    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _write_replay(tmp_path, 8 * 16)
    base = [
        "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu", "--master", "local[1]",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
    ]

    # control run: no serving anywhere
    ck_a = str(tmp_path / "ck_a")
    app.run(ConfArguments().parse(
        base + ["--checkpointDir", ck_a, "--checkpointEvery", "2"]
    ))
    control_state, control_meta = Checkpointer(ck_a).restore()

    # serving-live run: plane + promoter polling the ckpt dir mid-train
    ck_b = str(tmp_path / "ck_b")
    os.makedirs(ck_b)
    _save_ckpt(ck_b, 0, np.zeros(1004, np.float32), level="ok")
    snap, _ = load_servable(ck_b)
    plane = _plane(snap).start()
    promoter = SnapshotPromoter(ck_b, plane, poll_s=0.05).start()
    calls = {"n": 0}
    real_get = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real_get(x)

    jax.device_get = counting
    try:
        totals = app.run(ConfArguments().parse(
            base + ["--checkpointDir", ck_b, "--checkpointEvery", "2"]
        ))
    finally:
        jax.device_get = real_get
    assert totals["batches"] == 8
    assert calls["n"] == 8  # ONE fetch per train batch — serving added none
    # the promoter reached the train run's newest verified checkpoint.
    # Promotion is STAGED (poll) and applied between serve-loop dispatches
    # (the atomic-swap contract), so wait boundedly for the swap to land
    deadline = time.monotonic() + 10
    while plane.snapshot_step != totals["batches"] and (
        time.monotonic() < deadline
    ):
        promoter.poll_once()
        time.sleep(0.01)
    assert plane.snapshot_step == totals["batches"]
    promoter.stop()
    plane.stop()

    # bit-identity: identical final weights + counters either way
    serving_state, serving_meta = Checkpointer(ck_b).restore()
    assert serving_meta["count"] == control_meta["count"]
    assert np.array_equal(np.asarray(control_state),
                          np.asarray(serving_state))


# ---------------------------------------------------------------------------
# the HTTP front door + the serve entry point

def test_http_predict_roundtrip_and_503_without_plane(tmp_path):
    import urllib.request

    from twtml_tpu.serving.client import ServingError
    from twtml_tpu.web.cache import ApiCache
    from twtml_tpu.web.server import Server

    # no plane attached → 503 with a JSON error
    bare = Server(port=0, host="127.0.0.1",
                  cache=ApiCache(backup_file=str(tmp_path / "c1.json")))
    bare.start_background()
    try:
        url = f"http://127.0.0.1:{bare._runner.addresses[0][1]}"
        with pytest.raises(ServingError) as exc_info:
            ServingClient(url).predict([{"text": "x"}])
        assert exc_info.value.status == 503
    finally:
        bare.stop()

    w = _trained_weights()
    snap = ServingSnapshot(step=9, weights=w,
                           meta={"quality": {"level": "ok"}})
    plane = _plane(snap).start()
    srv = Server(port=0, host="127.0.0.1",
                 cache=ApiCache(backup_file=str(tmp_path / "c2.json")))
    srv.attach_serving(plane)
    srv.start_background()
    try:
        url = f"http://127.0.0.1:{srv._runner.addresses[0][1]}"
        client = ServingClient(url)
        res = client.predict([
            {"text": "served over http", "followers_count": 5,
             "created_at_ms": NOW_MS},
            "bare string row",
        ])
        assert res["snapshotStep"] == 9 and res["servedRows"] == 2
        assert len(res["predictions"]) == 2

        # a malformed body is a 400, not a 500/hang
        req = urllib.request.Request(
            url + "/api/predict", data=b'{"rows": 7}',
            headers={"content-type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as http_err:
            urllib.request.urlopen(req, timeout=5)
        assert http_err.value.code == 400

        # /api/serving: default view, then the published plane stats
        view = client.serving()
        assert view["jsonClass"] == "Serving" and view["snapshotStep"] == -1
        from twtml_tpu.telemetry.web_client import WebClient

        WebClient(url).serving(plane.stats())
        view = client.serving()
        assert view["snapshotStep"] == 9 and view["requests"] == 1
        assert view["level"] == "ok"
    finally:
        srv.stop()
        plane.stop()


def test_serve_app_end_to_end(tmp_path, monkeypatch):
    """The CI serve-smoke: boot apps.serve against a trained checkpoint
    directory, round-trip one predict over real HTTP, assert parity."""
    import jax

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    ck = tmp_path / "ck"
    w = _trained_weights()
    _save_ckpt(ck, 4, w, level="ok")

    from twtml_tpu.apps import serve as serve_app

    stop = threading.Event()
    ready = {}
    ready_evt = threading.Event()

    def started(server, plane, promoter):
        ready["port"] = server._runner.addresses[0][1]
        ready_evt.set()

    conf = ConfArguments().parse([
        "--backend", "cpu", "--master", "local[1]",
        "--checkpointDir", str(ck), "--servePort", "0",
        "--serveBatchRows", "32", "--serveMaxWaitMs", "2",
        "--servePromoteEvery", "600",
    ])
    result = {}

    def runner():
        result["stats"] = serve_app.run(conf, started=started,
                                        stop_event=stop)

    thread = threading.Thread(target=runner)
    thread.start()
    try:
        assert ready_evt.wait(timeout=300), "serve app never came up"
        client = ServingClient(f"http://127.0.0.1:{ready['port']}")
        statuses = _statuses(6, seed=2)
        rows = [{
            "text": s.retweeted_status.text,
            "followers_count": s.retweeted_status.followers_count,
            "favourites_count": s.retweeted_status.favourites_count,
            "friends_count": s.retweeted_status.friends_count,
            "created_at_ms": s.retweeted_status.created_at_ms,
        } for s in statuses]
        res = client.predict(rows)
        assert res["snapshotStep"] == 4 and res["servedRows"] == 6
    finally:
        stop.set()
        thread.join(timeout=120)
    assert not thread.is_alive()
    assert result["stats"]["requests"] == 1

    # parity through the full HTTP + JSON + plane stack
    batch = _feat().featurize_batch_ragged(
        statuses, row_bucket=32, pre_filtered=True
    )
    ref_model = StreamingLinearRegressionWithSGD().set_initial_weights(w)
    ref = np.asarray(jax.device_get(ref_model.step(batch)).predictions)[
        np.asarray(batch.mask) > 0
    ]
    assert np.array_equal(ref, np.asarray(res["predictions"], np.float32))


def test_serve_app_refuses_unservable_directory(tmp_path):
    from twtml_tpu.apps import serve as serve_app

    conf = ConfArguments().parse([
        "--backend", "cpu", "--checkpointDir", str(tmp_path / "nope"),
    ])
    with pytest.raises(SystemExit, match="no servable snapshot"):
        serve_app.run(conf)
    with pytest.raises(SystemExit, match="checkpointDir"):
        serve_app.run(ConfArguments().parse(["--backend", "cpu"]))


# ---------------------------------------------------------------------------
# telemetry view

def test_stats_view_shape_and_tenant_tiles():
    rng = np.random.default_rng(1)
    stack = (rng.standard_normal((2, 1004)) * 1e-3).astype(np.float32)
    snap = ServingSnapshot(step=2, weights=stack,
                           meta={"quality": {"level": "warn"}})
    plane = _plane(snap).start()
    try:
        plane.submit(_statuses(16)).result(timeout=240)
        view = plane.stats()
    finally:
        plane.stop()
    assert view["snapshotStep"] == 2 and view["level"] == "warn"
    assert view["requests"] == 1 and view["rows"] == 16
    assert view["qps"] > 0 and view["p99Ms"] > 0
    assert [t["tenant"] for t in view["tenants"]] == [0, 1]
    assert sum(t["rows"] for t in view["tenants"]) == 16
    # the view round-trips the Serving jsonClass wire
    from twtml_tpu.telemetry.api_types import decode, encode, Serving

    known = Serving.__dataclass_fields__
    msg = Serving(**{k: v for k, v in view.items() if k in known})
    back = decode(encode(msg))
    assert back == msg and back.tenants == view["tenants"]
