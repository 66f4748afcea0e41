"""Model & data observability plane (ISSUE 8): the in-step quality vector,
the host-side drift/trend watcher, and the web/checkpoint surfaces.

The laws under test, in the order the ISSUE states them:
- **zero added fetches / zero added collectives** with ``--modelWatch on``
  — asserted by COUNTING ``jax.device_get`` / ``process_allgather`` over a
  real app run and a real lockstep run (the PR 1/5 idiom);
- **off bit-parity**: the ``--modelWatch off`` step's output pytree is
  structurally the pre-quality (HEAD) program's, and the quality plane is
  observation-only — ON vs OFF weights, stats, and predictions bit-equal;
- **drift detection**: an injected synthetic feature/label shift alerts, a
  stationary stream stays ok (deterministic seeded series);
- **per-tenant quality == standalone-model quality** at M=4 (the tenant
  plane's lax.map bit-parity law extended to the new leaf);
- **checkpoint quality stamp** roundtrip + ``tools/model_report.py`` exit
  codes (0 well-formed, 2 malformed);
- the ``/api/model`` endpoint and the ModelHealth wire type.
"""

import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import model_report  # noqa: E402
from twtml_tpu.config import ConfArguments  # noqa: E402
from twtml_tpu.features.featurizer import Featurizer  # noqa: E402
from twtml_tpu.models import (  # noqa: E402
    StepOutput,
    StreamingLinearRegressionWithSGD,
)
from twtml_tpu.ops.quality import (  # noqa: E402
    QUALITY_FIELDS,
    QUALITY_INDEX,
    QUALITY_WIDTH,
)
from twtml_tpu.streaming.sources import SyntheticSource  # noqa: E402
from twtml_tpu.telemetry import metrics as _metrics  # noqa: E402
from twtml_tpu.telemetry import modelwatch as modelwatch_mod  # noqa: E402
from twtml_tpu.telemetry import tenants as _tenants_tel  # noqa: E402
from twtml_tpu.telemetry.modelwatch import ModelWatch  # noqa: E402

NOW_MS = 1785320000000


@pytest.fixture(autouse=True)
def _fresh_state():
    _metrics.reset_for_tests()
    modelwatch_mod.reset_for_tests()
    _tenants_tel.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    modelwatch_mod.reset_for_tests()
    _tenants_tel.reset_for_tests()


def _ragged_batches(n=256, b=128, seed=3):
    feat = Featurizer(now_ms=NOW_MS)
    statuses = list(SyntheticSource(total=n, seed=seed).produce())
    return [
        feat.featurize_batch_ragged(
            statuses[i : i + b], row_bucket=b, pre_filtered=True
        )
        for i in range(0, n, b)
    ]


# ---------------------------------------------------------------------------
# the in-step quality vector


def test_quality_vector_shape_fields_and_ranges():
    model = StreamingLinearRegressionWithSGD(quality=True)
    out = model.step(_ragged_batches()[0])
    q = np.asarray(out.quality)
    assert q.shape == (QUALITY_WIDTH,)
    assert q.dtype == np.float32
    assert np.isfinite(q).all()
    assert len(QUALITY_FIELDS) == QUALITY_WIDTH
    # norms are non-negative; first batch from zero weights:
    # ||w_new|| == ||w_new - 0||
    assert q[QUALITY_INDEX["weight_norm"]] == pytest.approx(
        q[QUALITY_INDEX["update_norm"]]
    )
    assert q[QUALITY_INDEX["grad_norm"]] > 0
    # occupancy is a fraction of folded bins; top share a mass fraction
    assert 0.0 <= q[QUALITY_INDEX["bucket_occupancy"]] <= 1.0
    assert 0.0 < q[QUALITY_INDEX["bucket_top_share"]] <= 1.0
    # label moments match the host's masked computation
    rb = _ragged_batches()[0]
    valid = np.asarray(rb.mask) > 0
    labels = np.asarray(rb.label, np.float64)[valid]
    assert q[QUALITY_INDEX["label_mean"]] == pytest.approx(
        labels.mean(), rel=1e-5
    )
    assert q[QUALITY_INDEX["label_var"]] == pytest.approx(
        labels.var(), rel=1e-4
    )


def _plane_batch(kind):
    """Four rows of token pairs at 512 text dims whose Gram gate
    (ops/gram.text_gram) takes the named plane."""
    from twtml_tpu.features.batch import NUM_NUMBER_FEATURES, FeatureBatch

    rng = np.random.default_rng(25)
    idx = np.zeros((4, 320), np.int32)
    val = np.zeros((4, 320), np.float32)
    idx[:, :20] = rng.integers(1, 512, size=(4, 20))
    val[:, :20] = 1.0
    if kind == "bf16":      # a row of 279 distinct bigrams: rung 2
        idx[0, :279], val[0, :279] = np.arange(1, 280), 1.0
    elif kind == "exact":   # one bigram 300 times over
        idx[0, :300], val[0, :300] = 7, 1.0
    return FeatureBatch(
        idx, val, rng.normal(size=(4, NUM_NUMBER_FEATURES)).astype(np.float32),
        rng.uniform(0, 50, size=(4,)).astype(np.float32), np.ones(4, np.float32),
    )


@pytest.mark.parametrize("kind, want", [
    ("bf16", 1.0), ("exact", 0.0), ("s8", 2.0), ("not_gram", -1.0),
])
def test_gram_plane_is_the_last_field_and_names_the_plane_taken(kind, want):
    """The counter of PR 25: the index ``text_gram``'s switch took rides
    the quality vector as its last field; −1 where the step is not in the
    Gram basis (the reference's 1,004-dim dense model)."""
    from twtml_tpu.models.sgd import make_sgd_train_step, zero_weights

    assert QUALITY_FIELDS[-1] == "gram_plane"
    assert QUALITY_INDEX["gram_plane"] == QUALITY_WIDTH - 1
    if kind == "not_gram":
        out = StreamingLinearRegressionWithSGD(quality=True).step(
            _ragged_batches()[0]
        )
    else:
        step = make_sgd_train_step(
            num_text_features=512, use_sparse=True, num_iterations=5,
            step_size=1e-6, quality=True,
        )
        _w, out = step(zero_weights(512), _plane_batch(kind))
    assert float(out.quality[QUALITY_INDEX["gram_plane"]]) == want


def test_watcher_keeps_the_gram_plane_as_a_gauge():
    """[M, Q]: the slowest plane any tenant's step took this tick."""
    q = np.zeros((2, QUALITY_WIDTH), np.float64)
    q[:, QUALITY_INDEX["gram_plane"]] = (2.0, 1.0)
    ModelWatch().observe(q, np.array([8.0, 8.0]), np.array([4.0, 2.0]))
    gauges = _metrics.get_registry().snapshot()["gauges"]
    assert gauges["model.gram_plane"] == 1.0


def test_off_program_is_structurally_head_and_observation_only():
    """ACCEPTANCE (off bit-parity): quality=False leaves the output pytree
    the HEAD 5-leaf StepOutput (the quality leaf is None — same compiled
    program structure), and the quality computation is a pure side channel:
    ON vs OFF weights, stats, and predictions are byte-identical."""
    import jax

    off = StreamingLinearRegressionWithSGD()
    on = StreamingLinearRegressionWithSGD(quality=True)
    batches = _ragged_batches()
    for rb in batches:
        o_off, o_on = off.step(rb), on.step(rb)
        assert o_off.quality is None
        assert o_on.quality is not None
        for f in ("count", "mse", "real_stdev", "pred_stdev"):
            assert np.asarray(getattr(o_off, f)).tobytes() == (
                np.asarray(getattr(o_on, f)).tobytes()
            ), f
        assert np.array_equal(
            np.asarray(o_off.predictions), np.asarray(o_on.predictions)
        )
    assert off.latest_weights.tobytes() == on.latest_weights.tobytes()
    # structural differential: the OFF output pytree has exactly the HEAD
    # leaf set; ON appends exactly one [QUALITY_WIDTH] leaf
    leaves_off = jax.tree_util.tree_leaves(off.step(batches[0]))
    leaves_on = jax.tree_util.tree_leaves(on.step(batches[0]))
    assert len(leaves_on) == len(leaves_off) + 1


def test_quality_rides_the_fetch_pipeline():
    """Under the pipeline every delivered batch carries ITS quality
    vector — bit-equal to a sequential step's, in dispatch order, on the
    packed wire the apps ship."""
    from twtml_tpu.apps.common import FetchPipeline

    model = StreamingLinearRegressionWithSGD(quality=True)
    seq = StreamingLinearRegressionWithSGD(quality=True)
    batches = _ragged_batches() * 3  # deeper than one window's worth
    got = []
    pipe = FetchPipeline(
        model, lambda out, b, t, at_boundary: got.append(out.quality),
        depth=8, pack=True,
    )
    for i, rb in enumerate(batches):
        pipe.on_batch(rb, float(i))
    pipe.flush()
    assert len(got) == len(batches)
    for k, rb in enumerate(batches):
        q = np.asarray(got[k])
        assert q.shape == (QUALITY_WIDTH,)
        assert np.asarray(seq.step(rb).quality).tobytes() == q.tobytes(), k


def test_mesh_quality_is_global_and_finite():
    import jax

    from twtml_tpu.parallel import ParallelSGDModel, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    pm = ParallelSGDModel(mesh, quality=True)
    single = StreamingLinearRegressionWithSGD(quality=True)
    rb = _ragged_batches()[0]
    qm = np.asarray(pm.step(rb).quality)
    qs = np.asarray(single.step(rb).quality)
    assert qm.shape == (QUALITY_WIDTH,)
    assert np.isfinite(qm).all()
    # psum-global moments match the single-device values (same math,
    # different reduction association)
    for f in ("label_mean", "label_var", "num_mean_0", "bucket_occupancy"):
        i = QUALITY_INDEX[f]
        assert qm[i] == pytest.approx(float(qs[i]), rel=1e-4), f


def test_m4_per_tenant_quality_bit_equals_standalone():
    """ACCEPTANCE: tenant m's quality vector bit-equals a standalone
    single-tenant model's on the routed sub-batches (the lax.map parity
    law extended to the new leaf)."""
    from twtml_tpu.features.batch import split_batch_tenants, tenant_route_keys
    from twtml_tpu.parallel import TenantStackModel

    m = 4
    mt = TenantStackModel(m, step_size=0.1, quality=True)
    singles = [
        StreamingLinearRegressionWithSGD(step_size=0.1, quality=True)
        for _ in range(m)
    ]
    for rb in _ragged_batches():
        parts = split_batch_tenants(rb, tenant_route_keys(rb, m), m)
        out = mt.step(rb)
        q = np.asarray(out.quality)
        assert q.shape == (m, QUALITY_WIDTH)
        for i in range(m):
            oi = singles[i].step(parts[i])
            assert np.asarray(oi.quality).tobytes() == q[i].tobytes(), i


# ---------------------------------------------------------------------------
# the drift / loss-trend detector (deterministic synthetic streams)


def _qvec(rng, label_mean=100.0, num0=5.0, weight_norm=50.0):
    q = np.zeros(QUALITY_WIDTH, np.float64)
    q[QUALITY_INDEX["weight_norm"]] = weight_norm + rng.normal(0, 0.5)
    q[QUALITY_INDEX["update_norm"]] = 1.0 + rng.normal(0, 0.1)
    q[QUALITY_INDEX["grad_norm"]] = 200.0 + rng.normal(0, 5.0)
    q[QUALITY_INDEX["pred_mean"]] = label_mean + rng.normal(0, 1.0)
    q[QUALITY_INDEX["pred_var"]] = 25.0
    q[QUALITY_INDEX["label_mean"]] = label_mean + rng.normal(0, 1.0)
    q[QUALITY_INDEX["label_var"]] = 25.0
    q[QUALITY_INDEX["resid_mean"]] = rng.normal(0, 0.5)
    q[QUALITY_INDEX["resid_var"]] = 4.0
    q[QUALITY_INDEX["num_mean_0"]] = num0 + rng.normal(0, 0.1)
    q[QUALITY_INDEX["bucket_occupancy"]] = 0.9
    q[QUALITY_INDEX["bucket_top_share"]] = 0.1 + rng.normal(0, 0.005)
    return q


def test_stationary_stream_stays_ok():
    rng = np.random.default_rng(7)
    watch = ModelWatch()
    for _ in range(300):
        v = watch.observe(_qvec(rng), 128.0, 100.0 + rng.normal(0, 2.0))
        assert v["level"] == "ok", v
    assert v["drift_score"] < modelwatch_mod.WARN_Z
    assert abs(v["loss_trend"]) < modelwatch_mod.TREND_WARN
    assert _metrics.get_registry().counter(
        "model.drift_episodes"
    ).snapshot() == 0


def test_injected_label_shift_alerts():
    """ACCEPTANCE: a 20σ label/prediction mean shift mid-stream crosses the
    alert threshold within one recent window; the episode is counted and
    the flight recorder sees the flip."""
    from twtml_tpu.telemetry import blackbox as blackbox_mod

    rec = blackbox_mod.install(config={"t": 1})
    try:
        rng = np.random.default_rng(7)
        watch = ModelWatch()
        for _ in range(150):
            v = watch.observe(_qvec(rng), 128.0, 100.0)
            assert v["level"] == "ok"
        levels = []
        for _ in range(modelwatch_mod.RECENT_WINDOW + 2):
            v = watch.observe(
                _qvec(rng, label_mean=120.0), 128.0, 100.0
            )
            levels.append(v["level"])
        assert levels[-1] == "alert", levels
        assert v["drift_score"] >= modelwatch_mod.ALERT_Z
        reg = _metrics.get_registry()
        assert reg.counter("model.drift_episodes").snapshot() >= 1
        assert reg.gauge("model.health_level").snapshot() == 2
        kinds = [e["kind"] for e in rec.bundle("t")["events"]]
        assert "model_health" in kinds and "drift_episode" in kinds
    finally:
        blackbox_mod.uninstall()


def test_feature_shift_alerts_via_numeric_moment():
    rng = np.random.default_rng(11)
    watch = ModelWatch()
    for _ in range(150):
        watch.observe(_qvec(rng), 128.0, 100.0)
    for _ in range(modelwatch_mod.RECENT_WINDOW + 2):
        v = watch.observe(_qvec(rng, num0=9.0), 128.0, 100.0)
    assert v["level"] == "alert"


def test_loss_trend_detector_ewma_slope():
    rng = np.random.default_rng(3)
    watch = ModelWatch()
    for _ in range(100):
        v = watch.observe(_qvec(rng), 128.0, 100.0)
    assert v["level"] == "ok"
    mse = 100.0
    seen = []
    for _ in range(60):
        mse *= 1.15  # exploding loss, stationary moments
        v = watch.observe(_qvec(rng), 128.0, mse)
        seen.append(v["level"])
    assert "alert" in seen  # the trend crossed TREND_ALERT
    assert v["loss_trend"] >= modelwatch_mod.TREND_ALERT


def test_nonfinite_quality_is_immediate_alert():
    rng = np.random.default_rng(5)
    watch = ModelWatch()
    q = _qvec(rng)
    q[QUALITY_INDEX["weight_norm"]] = math.nan
    v = watch.observe(q, 128.0, 100.0)
    assert v["level"] == "alert"
    assert v["alert_run"] == 1
    v = watch.observe(q, 128.0, 100.0)
    assert v["alert_run"] == 2
    # recovery: finite quality drops back to ok and resets the run
    v = watch.observe(_qvec(rng), 128.0, 100.0)
    assert v["level"] == "ok" and v["alert_run"] == 0


def test_per_tenant_tracks_and_worst_tenant_wins():
    rng = np.random.default_rng(9)
    watch = ModelWatch()
    for _ in range(150):
        q = np.stack([_qvec(rng), _qvec(rng, label_mean=50.0)])
        v = watch.observe(q, np.array([64.0, 64.0]), np.array([100.0, 90.0]))
        assert v["level"] == "ok"
    # only tenant 1 shifts: the model-level verdict follows the worst track
    for _ in range(modelwatch_mod.RECENT_WINDOW + 2):
        q = np.stack([_qvec(rng), _qvec(rng, label_mean=70.0)])
        v = watch.observe(q, np.array([64.0, 64.0]), np.array([100.0, 90.0]))
    assert v["level"] == "alert"
    view = watch.view()
    assert [t["level"] for t in view["tenants"]] == ["ok", "alert"]
    reg = _metrics.get_registry()
    assert reg.gauge("tenant.1.health_level").snapshot() == 2
    assert reg.gauge("tenant.0.health_level").snapshot() == 0


def test_view_and_checkpoint_snapshot_shapes():
    rng = np.random.default_rng(1)
    assert modelwatch_mod.last_model() is None
    assert modelwatch_mod.snapshot_for_checkpoint() is None
    for _ in range(4):
        modelwatch_mod.record_tick(_qvec(rng), 128.0, 50.0)
    view = modelwatch_mod.last_model()
    assert view["level"] == "ok"
    assert len(view["mse"]) == 4 and view["ticks"] == 4
    assert view["tenants"] == []  # single model: no per-tenant rows
    snap = modelwatch_mod.snapshot_for_checkpoint()
    assert snap["level"] == "ok" and snap["ticks"] == 4
    json.dumps(snap)  # json-safe (checkpoint meta + bundles carry it)


# ---------------------------------------------------------------------------
# the sentinel early-warning hook (forced verified-checkpoint save)


class _FakeCkpt:
    def __init__(self):
        self.saves = 0

    def save_now(self, totals):
        self.saves += 1
        return True


def test_sustained_alert_forces_one_checkpoint_per_episode():
    from twtml_tpu.apps.common import ModelWatchGuard
    from twtml_tpu.telemetry import blackbox as blackbox_mod

    rec = blackbox_mod.install(config={"t": 1})
    try:
        conf = ConfArguments().parse(["--modelWatchWindow", "3"])
        ckpt = _FakeCkpt()
        guard = ModelWatchGuard(conf, ckpt, {"count": 0, "batches": 0})
        rng = np.random.default_rng(2)
        bad = _qvec(rng)
        bad[QUALITY_INDEX["grad_norm"]] = math.inf  # nonfinite → alert
        out_bad = StepOutput(
            predictions=None, count=np.float32(64), mse=np.float32(1.0),
            real_stdev=np.float32(1.0), pred_stdev=np.float32(1.0),
            quality=bad,
        )
        for _ in range(2):
            guard.observe(out_bad)
        assert ckpt.saves == 0  # window (3) not reached yet
        guard.observe(out_bad)
        assert ckpt.saves == 1  # forced save at the window
        for _ in range(5):
            guard.observe(out_bad)
        assert ckpt.saves == 1  # ONE save per episode, not per batch
        good = StepOutput(
            predictions=None, count=np.float32(64), mse=np.float32(1.0),
            real_stdev=np.float32(1.0), pred_stdev=np.float32(1.0),
            quality=_qvec(rng),
        )
        guard.observe(good)  # episode closes
        for _ in range(3):
            guard.observe(out_bad)
        assert ckpt.saves == 2  # a NEW episode earns a new save
        reg = _metrics.get_registry()
        assert reg.counter("model.alert_checkpoints").snapshot() == 2
        kinds = [e["kind"] for e in rec.bundle("t")["events"]]
        assert kinds.count("modelwatch_alert_checkpoint") == 2
    finally:
        blackbox_mod.uninstall()


def test_guard_disabled_and_missing_quality_are_noops():
    from twtml_tpu.apps.common import ModelWatchGuard

    conf_off = ConfArguments().parse(["--modelWatch", "off"])
    guard = ModelWatchGuard(conf_off, _FakeCkpt(), {"batches": 0})
    assert not guard.enabled
    out = StepOutput(
        predictions=None, count=np.float32(4), mse=np.float32(1.0),
        real_stdev=np.float32(1.0), pred_stdev=np.float32(1.0),
    )
    guard.observe(out)  # must not raise
    guard_on = ModelWatchGuard(
        ConfArguments(), _FakeCkpt(), {"batches": 0}
    )
    guard_on.observe(out)  # quality=None → no-op
    assert modelwatch_mod.last_model() is None


# ---------------------------------------------------------------------------
# THE acceptance constraint: zero added fetches / zero added collectives
# with --modelWatch on, counted over real runs (the PR 1/5 law)


def test_modelwatch_adds_no_fetches_and_no_collectives(monkeypatch):
    import jax
    from jax.experimental import multihost_utils

    from twtml_tpu.apps.common import FetchPipeline, ModelWatchGuard
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.streaming.context import StreamingContext

    jax.devices()  # lock the conftest backend
    calls = {"allgather": 0, "get": 0}
    real_ag = multihost_utils.process_allgather

    def counting_ag(arr):
        calls["allgather"] += 1
        return real_ag(arr)

    monkeypatch.setattr(multihost_utils, "process_allgather", counting_ag)
    real_get = jax.device_get

    def counting_get(x):
        calls["get"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)

    ssc = StreamingContext(batch_interval=0)
    stream = ssc.source_stream(
        SyntheticSource(total=64, seed=7, base_ms=NOW_MS),
        Featurizer(now_ms=NOW_MS),
        row_bucket=16, token_bucket=64, device_hash=True,
    )
    model = StreamingLinearRegressionWithSGD(num_iterations=2, quality=True)
    guard = ModelWatchGuard(
        ConfArguments(), None, {"count": 0, "batches": 0}
    )

    def handle(out, b, t, at_boundary=True):
        guard.observe(out, at_boundary=at_boundary)

    pipe = FetchPipeline(model, handle, deterministic=True)
    stream.foreach_batch(pipe.on_batch)
    ssc.start(lockstep=True)
    assert ssc.await_termination(timeout=120)
    ssc.stop()
    pipe.flush()
    assert not ssc.failed
    assert ssc.batches_processed >= 4

    reg = _metrics.get_registry().snapshot()
    ticks = reg["counters"]["lockstep.ticks"]
    # ZERO added collectives: still exactly ONE allgather per lockstep tick
    assert calls["allgather"] == ticks
    # ZERO added host fetches: one per dispatched batch — the quality leaf
    # rides the StepOutput transfer, the watcher never touches the device
    assert calls["get"] == ssc.batches_processed
    view = modelwatch_mod.last_model()
    assert view is not None and view["ticks"] == ssc.batches_processed


CLOSED = "http://127.0.0.1:9"
BASE = [
    "--source", "replay", "--seconds", "0", "--backend", "cpu",
    "--batchBucket", "16", "--tokenBucket", "64", "--master", "local[1]",
    "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
]


def _corpus_file(tmp_path, total=8 * 16, seed=51):

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in SyntheticSource(
            total=total, seed=seed, base_ms=NOW_MS
        ).produce():
            fh.write(json.dumps(s.to_json()) + "\n")
    return path


def _run_counting_fetches(conf_args):
    import jax

    from twtml_tpu.apps import linear_regression as app

    jax.devices()
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    jax.device_get = counting
    try:
        totals = app.run(ConfArguments().parse(list(conf_args)))
    finally:
        jax.device_get = real
    return totals, calls["n"]


def test_app_default_modelwatch_one_fetch_per_tick(tmp_path, monkeypatch):
    """ACCEPTANCE: a real app run with the DEFAULT --modelWatch on fetches
    exactly once per dispatched batch, the watcher records every tick, and
    the checkpoint meta carries the quality stamp."""
    from twtml_tpu.checkpoint import Checkpointer

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)
    totals, fetches = _run_counting_fetches(
        BASE + ["--replayFile", str(path),
                "--checkpointDir", str(tmp_path / "ck"),
                "--checkpointEvery", "1"]
    )
    assert totals["batches"] == 8
    assert fetches == 8  # ONE device_get per tick, quality riding along
    view = modelwatch_mod.last_model()
    assert view is not None and view["ticks"] == 8
    assert view["level"] == "ok"  # short healthy stream: no verdict drama
    reg = _metrics.get_registry().snapshot()
    assert reg["gauges"]["model.weight_norm"] > 0
    # checkpoint quality-stamp roundtrip (ACCEPTANCE)
    _, meta = Checkpointer(str(tmp_path / "ck")).restore()
    assert meta["quality"]["level"] == "ok"
    assert meta["quality"]["ticks"] >= 1
    assert meta["quality"]["weight_norm"] > 0
    # tools/model_report renders the history (exit 0) and --json parses
    assert model_report.main([str(tmp_path / "ck")]) == 0
    assert model_report.main([str(tmp_path / "ck"), "--json"]) == 0


def test_app_modelwatch_off_records_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)
    totals, fetches = _run_counting_fetches(
        BASE + ["--replayFile", str(path), "--modelWatch", "off"]
    )
    assert totals["batches"] == 8
    assert fetches == 8
    assert modelwatch_mod.last_model() is None


def test_app_m4_per_tenant_quality_rides_one_fetch(tmp_path, monkeypatch):
    """The tenant plane's [M, Q] quality leaf rides the ONE stacked fetch:
    per-tenant drift tracks materialize with the fetch count unchanged."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)
    totals, fetches = _run_counting_fetches(
        BASE + ["--replayFile", str(path), "--tenants", "4"]
    )
    assert totals["batches"] == 8 and totals["tenants"] == 4
    assert fetches == 8  # ONE device_get per tick, M=4 and quality riding
    view = modelwatch_mod.last_model()
    assert view is not None and len(view["tenants"]) == 4
    reg = _metrics.get_registry().snapshot()
    assert "tenant.0.health_level" in reg["gauges"]


# ---------------------------------------------------------------------------
# tools/model_report.py exit codes (the CHECK contract)


def test_model_report_malformed_exits_2(tmp_path):
    assert model_report.main([str(tmp_path / "absent")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert model_report.main([str(empty)]) == 2
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "ckpt-000000000001.npz").write_text("not an archive")
    assert model_report.main([str(bad)]) == 2
    assert model_report.main([]) == 2


def test_model_report_renders_unstamped_and_quarantined(tmp_path):
    from twtml_tpu.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path))
    ck.save(1, np.zeros(8, np.float32), {"count": 16})  # no quality stamp
    ck.save(2, np.full(8, np.nan, np.float32), {"count": 32})  # quarantined
    rows = model_report.load_history(str(tmp_path))
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["quality"] is None and not rows[0]["quarantined"]
    assert rows[1]["quarantined"] and not rows[1]["finite"]
    text = model_report.render(rows)
    assert "(unstamped)" in text and "QUARANTINED" in text
    assert model_report.main([str(tmp_path)]) == 0


# ---------------------------------------------------------------------------
# the ModelHealth wire type + /api/model


def test_model_health_wire_roundtrip():
    from twtml_tpu.telemetry.api_types import ModelHealth, decode, encode

    msg = ModelHealth(
        level="warn", driftScore=5.2, lossTrend=0.31, weightNorm=120.5,
        updateNorm=3.25, gradNorm=4000.0, mse=[10.0, 11.0],
        tenants=[{"tenant": 0, "level": "warn", "drift": 5.2}], episodes=2,
    )
    wire = encode(msg)
    assert json.loads(wire)["jsonClass"] == "ModelHealth"
    assert decode(wire) == msg


def test_api_model_endpoint_and_cache_dispatch(tmp_path):
    from twtml_tpu.telemetry.api_types import ModelHealth
    from twtml_tpu.telemetry.web_client import WebClient
    from twtml_tpu.web.cache import ApiCache
    from twtml_tpu.web.server import Server

    cache = ApiCache(backup_file=str(tmp_path / "twtml-web.json"))
    srv = Server(port=0, host="127.0.0.1", cache=cache)
    srv.start_background()
    try:
        port = srv._runner.addresses[0][1]
        url = f"http://127.0.0.1:{port}"
        client = WebClient(url)
        # default before any post: a well-formed empty ModelHealth
        import urllib.request

        with urllib.request.urlopen(url + "/api/model", timeout=2) as resp:
            doc = json.loads(resp.read())
        assert doc["jsonClass"] == "ModelHealth" and doc["level"] == "ok"
        client.model_health(
            level="alert", drift_score=9.5, loss_trend=1.4,
            weight_norm=100.0, update_norm=2.0, grad_norm=500.0,
            mse=[5.0, 6.0, 7.0],
            tenants=[{"tenant": 1, "level": "alert", "drift": 9.5}],
            episodes=3,
        )
        with urllib.request.urlopen(url + "/api/model", timeout=2) as resp:
            doc = json.loads(resp.read())
        assert doc["level"] == "alert"
        assert doc["driftScore"] == 9.5
        assert doc["mse"] == [5.0, 6.0, 7.0]
        assert doc["tenants"][0]["tenant"] == 1
        assert doc["episodes"] == 3
        assert isinstance(cache._model, ModelHealth)
    finally:
        srv.stop()


def test_session_stats_publishes_model_health_and_host_gauges(monkeypatch):
    """publish_metrics ships the modelwatch view as a ModelHealth message
    and samples the host gauges (RSS + uptime) each publish tick."""
    from twtml_tpu.telemetry.session_stats import SessionStats

    sent = []

    class _Conf:
        lightning = CLOSED
        twtweb = CLOSED
        webTimeout = 0.2

    session = SessionStats(_Conf())
    monkeypatch.setattr(
        session.web, "model_health", lambda **kw: sent.append(kw)
    )
    monkeypatch.setattr(session.web, "metrics", lambda *a, **k: None)
    rng = np.random.default_rng(4)
    modelwatch_mod.record_tick(_qvec(rng), 128.0, 42.0)
    session.publish_metrics()
    assert len(sent) == 1
    assert sent[0]["level"] == "ok" and sent[0]["mse"] == [42.0]
    reg = _metrics.get_registry().snapshot()
    assert reg["gauges"]["host.rss_mb"] > 0
    assert reg["gauges"]["host.uptime_s"] >= 0


# ---------------------------------------------------------------------------
# conf flags


def test_conf_flags():
    conf = ConfArguments()
    assert conf.modelWatch == "on" and conf.modelWatchWindow == 8
    conf = ConfArguments().parse(
        ["--modelWatch", "off", "--modelWatchWindow", "16"]
    )
    assert conf.modelWatch == "off" and conf.modelWatchWindow == 16
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--modelWatch", "bogus"])
