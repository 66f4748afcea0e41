"""Divergence sentinel (ISSUE 4 tentpole, part 2): a poisoned batch drives
the fused predict-then-train step's weights non-finite in ONE update; the
sentinel catches it on the ALREADY-FETCHED per-batch stats (zero added host
fetches — asserted the way the --trace tests do), rolls the model back to
the last verified-finite checkpoint, skips the poisoning batch, and after N
rollbacks in a window aborts cleanly through the ssc.request_abort path.

Acceptance (ISSUE 4): a --chaos 'source.nan(...)' run detects, rolls back,
continues — and its final weights MATCH a clean run over a replay file that
never contained the poisoned batch."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from twtml_tpu.config import ConfArguments
from twtml_tpu.streaming import faults
from twtml_tpu.telemetry import metrics as _metrics


@pytest.fixture(autouse=True)
def clean_state():
    _metrics.reset_for_tests()
    faults.uninstall_chaos()
    yield
    faults.uninstall_chaos()
    _metrics.reset_for_tests()


# -- unit: the admit()/rollback state machine --------------------------------

def _out(mse=1.0, real=2.0, pred=3.0, count=16):
    return SimpleNamespace(
        mse=mse, real_stdev=real, pred_stdev=pred, count=count
    )


class _FakeCkpt:
    def __init__(self, meta=None):
        self.meta = meta
        self.calls = 0

    def rollback_to_verified(self):
        self.calls += 1
        return self.meta


class _FakeSsc:
    def __init__(self):
        self.aborted = False
        self.rollback_count_fn = None

    def request_abort(self):
        self.aborted = True


class _FakeModel:
    def __init__(self):
        self.set_calls = []

    def set_initial_weights(self, w):
        self.set_calls.append(np.asarray(w))


def _sentinel(conf_args=(), ckpt=None, model=None, ssc=None):
    from twtml_tpu.apps.common import DivergenceSentinel

    conf = ConfArguments().parse(list(conf_args))
    ssc = ssc or _FakeSsc()
    s = DivergenceSentinel(
        conf, model or _FakeModel(), ckpt or _FakeCkpt({"step": 7}), ssc
    )
    return s, ssc


def test_finite_batches_admit_and_cost_nothing_extra():
    s, _ = _sentinel()
    assert s.enabled
    for _ in range(10):
        assert s.admit(_out(), None)
    assert s.rollbacks == 0


def test_nonfinite_rolls_back_once_per_episode_and_skips_tainted():
    ckpt = _FakeCkpt({"step": 4})
    s, ssc = _sentinel(ckpt=ckpt)
    assert s.admit(_out(), None)
    # poisoned batch + two in-flight batches trained on poisoned weights
    assert not s.admit(_out(mse=float("nan")), None)
    assert not s.admit(_out(pred=float("inf")), None)
    assert not s.admit(_out(mse=float("nan")), None)
    assert ckpt.calls == 1  # ONE rollback for the whole episode
    assert s.rollbacks == 1
    # first finite delivery closes the episode; a later NaN is a NEW one
    assert s.admit(_out(), None)
    assert not s.admit(_out(real=float("nan")), None)
    assert ckpt.calls == 2
    assert not ssc.aborted
    reg = _metrics.get_registry()
    assert reg.counter("model.rollbacks").snapshot() == 2
    assert reg.counter("model.nonfinite_batches").snapshot() == 4
    assert reg.counter("model.rows_lost").snapshot() == 4 * 16


def test_no_verified_checkpoint_resets_to_initial_zeros():
    model = _FakeModel()
    s, _ = _sentinel(ckpt=_FakeCkpt(None), model=model)
    assert not s.admit(_out(mse=float("nan")), None)
    assert len(model.set_calls) == 1
    w = model.set_calls[0]
    assert w.shape == (1000 + 4,)  # numTextFeatures default + numeric
    assert not w.any()


def test_rollback_storm_aborts_via_request_abort():
    s, ssc = _sentinel(conf_args=["--sentinelRollbacks", "2",
                                  "--sentinelWindow", "100"])
    assert not s.admit(_out(mse=float("nan")), None)  # rollback 1
    assert s.admit(_out(), None)
    assert not ssc.aborted
    assert not s.admit(_out(mse=float("nan")), None)  # rollback 2 -> abort
    assert ssc.aborted
    assert _metrics.get_registry().counter(
        "model.sentinel_aborts").snapshot() == 1


def test_rollbacks_outside_the_window_do_not_abort():
    s, ssc = _sentinel(conf_args=["--sentinelRollbacks", "2",
                                  "--sentinelWindow", "3"])
    assert not s.admit(_out(mse=float("nan")), None)
    for _ in range(5):  # slide the first rollback out of the window
        assert s.admit(_out(), None)
    assert not s.admit(_out(mse=float("nan")), None)
    assert not ssc.aborted
    assert s.rollbacks == 2


def test_sentinel_off_is_inert():
    s, ssc = _sentinel(conf_args=["--sentinel", "off"])
    assert not s.enabled
    assert ssc.rollback_count_fn is None


def test_rollback_count_rides_the_ssc_hook():
    s, ssc = _sentinel()
    assert ssc.rollback_count_fn() == 0
    s.admit(_out(mse=float("nan")), None)
    assert ssc.rollback_count_fn() == 1


# -- end-to-end acceptance ---------------------------------------------------

CLOSED = "http://127.0.0.1:9"


def _write_lines(path, lines):
    with open(path, "w") as fh:
        for ln in lines:
            fh.write(ln + "\n")


def _corpus(total, seed):
    from twtml_tpu.streaming.sources import SyntheticSource

    return [
        json.dumps(s.to_json())
        for s in SyntheticSource(
            total=total, seed=seed, base_ms=1785320000000
        ).produce()
    ]


def _run_counting_fetches(conf_args):
    """app.run with every jax.device_get counted — the measurement-
    integrity assertion idiom from tests/test_trace.py."""
    import jax

    from twtml_tpu.apps import linear_regression as app

    jax.devices()  # lock the conftest backend before local[1]
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


BASE = [
    "--source", "replay", "--seconds", "0", "--backend", "cpu",
    "--batchBucket", "16", "--tokenBucket", "64", "--master", "local[1]",
    "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
]


def test_acceptance_nan_chaos_rollback_matches_clean_run(tmp_path, monkeypatch):
    """THE ISSUE 4→19 acceptance path: poison batch 5 of 8 via source.nan,
    detect on the already-fetched stats, roll back to the verified
    checkpoint at batch 4, and RE-INGEST the skipped rows from the intake
    journal (--journal auto follows --checkpointDir). source.nan injects
    at the featurize stage — AFTER the journal seam — so the journaled
    bytes are clean and the trigger's call index never re-fires on the
    replay: the final weights and counters equal a clean run over the SAME
    full file. Crash-equals-clean, zero rows lost."""
    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    # pin the age-feature clock: the comparison is BIT-exact, and the two
    # runs must featurize identically (same trick as the multi-host tests)
    monkeypatch.setenv("TWTML_NOW_MS", "1785320000000")

    lines = _corpus(8 * 16, seed=51)
    poisoned_file = tmp_path / "poisoned.jsonl"
    _write_lines(poisoned_file, lines)

    d_poison, d_clean = str(tmp_path / "ckp"), str(tmp_path / "ckc")
    totals_p, fetches_p = _run_counting_fetches(
        BASE + ["--replayFile", str(poisoned_file),
                "--checkpointDir", d_poison, "--checkpointEvery", "1",
                "--chaos", "source.nan@5"]
    )
    reg = _metrics.get_registry()
    assert reg.counter("model.rollbacks").snapshot() == 1
    assert reg.counter("model.nonfinite_batches").snapshot() == 1
    # the journal converts the counted loss into a replay: the poisoned
    # batch's 16 rows re-ingest from cursor 4 and train clean
    assert reg.counter("model.rows_lost").snapshot() == 0
    assert reg.counter("journal.replayed_rows").snapshot() == 16
    assert reg.counter("journal.torn_tails").snapshot() == 0
    assert reg.counter("fetch.aborts").snapshot() == 0
    # one fetch per DISPATCHED batch and nothing else: 8 from the file +
    # 1 re-dispatch of the replayed rows — the sentinel and the journal
    # both read only what was already on the host
    assert fetches_p == 9
    # every row trains exactly once: the full-file ledger
    assert totals_p["batches"] == 8
    assert totals_p["count"] == 8 * 16

    _metrics.reset_for_tests()
    faults.uninstall_chaos()  # the injector is process-wide per --chaos run

    totals_c = app.run(ConfArguments().parse(
        BASE + ["--replayFile", str(poisoned_file),
                "--checkpointDir", d_clean, "--checkpointEvery", "1"]
    ))
    assert totals_c["batches"] == 8
    assert totals_c["count"] == 8 * 16

    w_poison, meta_p = Checkpointer(d_poison).restore()
    w_clean, meta_c = Checkpointer(d_clean).restore()
    assert meta_p["count"] == meta_c["count"] == 8 * 16
    # rollback restore is bit-exact, the journaled bytes are the clean
    # pre-poison rows, and replay re-runs them through the unchanged
    # featurize path in order -> identical trajectories
    np.testing.assert_array_equal(w_poison, w_clean)


def test_nan_chaos_zero_fetch_delta_vs_sentinel_off(tmp_path):
    """Healthy path: sentinel on vs off is fetch-count identical (the
    guard never touches the device)."""
    path = tmp_path / "tweets.jsonl"
    _write_lines(path, _corpus(4 * 16, seed=52))
    args = BASE + ["--replayFile", str(path)]
    totals_on, fetches_on = _run_counting_fetches(args)
    _metrics.reset_for_tests()
    totals_off, fetches_off = _run_counting_fetches(
        args + ["--sentinel", "off"]
    )
    assert totals_on["count"] == totals_off["count"] == 4 * 16
    assert fetches_on == fetches_off == 4


def test_nan_chaos_without_checkpoint_resets_and_continues(tmp_path):
    """No --checkpointDir: the rollback target is the reference's initial
    zeros — progress is lost loudly, the stream keeps training."""
    from twtml_tpu.apps import linear_regression as app

    import jax

    jax.devices()
    path = tmp_path / "tweets.jsonl"
    _write_lines(path, _corpus(8 * 16, seed=53))
    totals = app.run(ConfArguments().parse(
        BASE + ["--replayFile", str(path), "--chaos", "source.nan@5"]
    ))
    reg = _metrics.get_registry()
    assert reg.counter("model.rollbacks").snapshot() == 1
    # without a checkpoint cadence the fetch pipeline runs deep: between
    # the poisoned dispatch and its delivery, up to depth-1 more batches
    # trained on NaN weights and drain as tainted skips — how many is
    # wall-clock-dependent (the opportunistic early emit), so assert the
    # closed accounting instead of a fixed count
    lost = int(reg.counter("model.rows_lost").snapshot())
    assert lost >= 16
    assert totals["count"] == 8 * 16 - lost
    assert totals["batches"] == totals["count"] // 16
    assert reg.counter("model.sentinel_aborts").snapshot() == 0


def test_nan_storm_aborts_cleanly_with_finite_checkpoint(tmp_path):
    """Rollback storm (every 2nd batch poisoned, budget 2): the run aborts
    through request_abort — non-zero outcome, critical log, and the final
    checkpoint holds FINITE weights (the rollback restored them before
    the abort; a NaN final save would have been quarantined anyway)."""
    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    import jax

    jax.devices()
    path = tmp_path / "tweets.jsonl"
    _write_lines(path, _corpus(8 * 16, seed=54))
    ck = str(tmp_path / "ck")
    with pytest.raises(RuntimeError, match="runtime guard"):
        app.run(ConfArguments().parse(
            BASE + ["--replayFile", str(path),
                    "--checkpointDir", ck, "--checkpointEvery", "1",
                    "--chaos", "source.nan@2",
                    "--sentinelRollbacks", "2", "--sentinelWindow", "100"]
        ))
    reg = _metrics.get_registry()
    assert reg.counter("model.rollbacks").snapshot() == 2
    assert reg.counter("model.sentinel_aborts").snapshot() == 1
    restored = Checkpointer(ck).restore()
    assert restored is not None
    state, meta = restored
    assert np.isfinite(np.asarray(state)).all()


def test_poison_inside_a_full_inflight_window_rolls_back_once(tmp_path):
    """The poisoning lands while the fetch pipeline holds a full window:
    slow fetches (``fetch:delay``) keep 8 dispatched batches in flight, so
    the batches dispatched BEHIND the poisoned one trained on NaN weights
    and deliver as tainted skips of the SAME episode — one rollback to the
    batch-8 save, the journal re-ingests everything past it, and the run
    completes with the full-file ledger."""
    from twtml_tpu.apps import linear_regression as app

    import jax

    jax.devices()
    path = tmp_path / "tweets.jsonl"
    _write_lines(path, _corpus(16 * 16, seed=55))
    ck = str(tmp_path / "ck")
    totals = app.run(ConfArguments().parse(
        BASE + ["--replayFile", str(path),
                "--checkpointDir", ck, "--checkpointEvery", "8",
                # @13 fires on the 13th featurize call only: the replays
                # bring the total to at most 24 calls, short of the 26th
                "--chaos", "fetch:delay=0.02,source.nan@13"]
    ))
    reg = _metrics.get_registry()
    assert reg.counter("model.rollbacks").snapshot() == 1
    # batch 13 and every batch dispatched behind it before its delivery
    assert reg.counter("model.nonfinite_batches").snapshot() >= 2
    assert reg.counter("fetch.aborts").snapshot() == 0
    assert reg.counter("model.sentinel_aborts").snapshot() == 0
    assert totals["batches"] == 16
    assert totals["count"] == 16 * 16
    assert reg.counter("model.rows_lost").snapshot() == 0
    # the rollback point is the batch-8 save: batches 9..13 at the least
    assert reg.counter("journal.replayed_rows").snapshot() >= 5 * 16
