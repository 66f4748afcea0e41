"""Transport chaos harness (streaming/faults.ChaosInjector + ``--chaos``)
and the end-to-end behavior it exists to prove: a run SURVIVES injected
fetch/dispatch faults and publish outages (retries + breaker, no hang, no
lost rows), and a run whose transport wedges for good aborts CLEANLY with
a checkpoint a restarted run resumes from — the ISSUE 2 acceptance
criteria."""

import json
import time

import numpy as np
import pytest

from twtml_tpu.config import ConfArguments
from twtml_tpu.streaming import faults
from twtml_tpu.streaming.faults import ChaosInjector, InjectedFault
from twtml_tpu.streaming.sources import SyntheticSource
from twtml_tpu.telemetry import metrics as _metrics


@pytest.fixture(autouse=True)
def clean_chaos():
    _metrics.reset_for_tests()
    faults.uninstall_chaos()
    yield
    faults.uninstall_chaos()
    _metrics.reset_for_tests()


def _fires(inj, target, calls):
    out = []
    for _ in range(calls):
        try:
            inj.perturb(target)
            out.append(False)
        except InjectedFault:
            out.append(True)
    return out


# -- spec parsing + injection semantics --------------------------------------

def test_every_nth_trigger_is_deterministic():
    fired = _fires(ChaosInjector("fetch:error@3"), "fetch", 9)
    assert [i + 1 for i, f in enumerate(fired) if f] == [3, 6, 9]


def test_from_trigger_is_a_permanent_outage():
    fired = _fires(ChaosInjector("step:error@from4"), "step", 6)
    assert fired == [False, False, False, True, True, True]


def test_delay_rule_sleeps_and_counts():
    inj = ChaosInjector("fetch:delay=0.05@2")
    t0 = time.perf_counter()
    for _ in range(4):
        inj.perturb("fetch")  # delays on calls 2 and 4
    assert time.perf_counter() - t0 >= 0.1
    reg = _metrics.get_registry()
    assert reg.counter("chaos.fetch.delays").snapshot() == 2
    assert reg.counter("chaos.injected").snapshot() == 2


def test_probability_trigger_is_seeded_deterministic():
    spec = "web:error@p0.5,seed=9"
    a = _fires(ChaosInjector(spec), "web", 50)
    b = _fires(ChaosInjector(spec), "web", 50)
    assert a == b
    assert 5 < sum(a) < 45  # actually probabilistic, not all-or-nothing


def test_targets_are_independent():
    inj = ChaosInjector("fetch:error@1")
    inj.perturb("web")  # no web rules: untouched
    inj.perturb("step")
    with pytest.raises(InjectedFault):
        inj.perturb("fetch")


@pytest.mark.parametrize("bad", [
    "",  # no rules
    "seed=3",  # seed alone
    "nonsense",  # no target:action
    "gpu:error",  # unknown target
    "fetch:frob=1",  # unknown action
    "fetch:delay=0",  # non-positive delay
    "fetch:delay=abc",  # unparseable value
    "fetch:error@p0",  # probability out of range
    "fetch:error@0",  # every-0th
    "fetch:error@from0",  # from-0th
    "fetch",  # transport targets need an action
    "source.nan:error",  # source targets take no action
    "source.nan:rows=4",  # rows= is burst-only
    "source.garbage:delay=1",  # no transport actions on source targets
    "source.burst:rows=0",  # non-positive burst
    "source.frob",  # unknown source target
])
def test_malformed_specs_are_rejected(bad):
    with pytest.raises(ValueError):
        ChaosInjector(bad)


# -- source-chaos grammar (r7: the ingest-guard failure domain) --------------

def test_source_targets_parse_bare_with_trigger():
    inj = ChaosInjector("source.nan@3")
    fired = [inj.should("source.nan") is not None for _ in range(9)]
    assert [i + 1 for i, f in enumerate(fired) if f] == [3, 6, 9]
    reg = _metrics.get_registry()
    assert reg.counter("chaos.source.nan.injected").snapshot() == 3
    assert reg.counter("chaos.injected").snapshot() == 3


def test_burst_rows_magnitude_and_default():
    inj = ChaosInjector("source.burst:rows=8@2")
    assert inj.should("source.burst") is None
    assert inj.should("source.burst") == 8
    inj = ChaosInjector("source.burst")
    assert inj.should("source.burst") == faults.BURST_DEFAULT_EXTRA


def test_should_never_raises_or_sleeps():
    inj = ChaosInjector("source.garbage@1")
    t0 = time.perf_counter()
    for _ in range(100):
        assert inj.should("source.garbage") == faults.BURST_DEFAULT_EXTRA
    assert time.perf_counter() - t0 < 0.5
    assert inj.should("fetch") is None  # no rules for that target


def test_source_and_transport_rules_compose():
    inj = ChaosInjector("fetch:error@2,source.nan@2")
    inj.perturb("fetch")
    with pytest.raises(InjectedFault):
        inj.perturb("fetch")
    assert inj.should("source.nan") is None
    assert inj.should("source.nan") is not None


def test_poison_labels_touches_only_valid_rows():
    faults.install_chaos("source.nan@1")
    from twtml_tpu.features.featurizer import Featurizer

    statuses = list(
        SyntheticSource(total=5, seed=1, base_ms=1785320000000).produce()
    )
    batch = Featurizer(now_ms=1785320000000).featurize_batch_units(
        statuses, row_bucket=8, unit_bucket=64, pre_filtered=True
    )
    poisoned = faults.maybe_poison_labels(batch)
    valid = np.asarray(batch.mask) > 0
    assert np.isnan(poisoned.label[valid]).all()
    # padding labels stay zero: the learner multiplies by mask, and NaN
    # padding would taint every batch
    assert (poisoned.label[~valid] == 0).all()
    assert not np.isnan(np.asarray(batch.label)).any()  # input untouched


def test_corrupt_block_skips_tiny_buffers():
    faults.install_chaos("source.garbage@1")
    tiny = b'{"x": 1}\n'
    assert faults.maybe_corrupt_block(tiny) == tiny  # under the 256B floor
    big = b"x" * 1024
    out = faults.maybe_corrupt_block(big)
    assert len(out) < len(big)
    assert out != big[: len(out)]  # garbled, not just truncated


def test_bad_chaos_flag_is_a_loud_exit():
    from twtml_tpu.apps.common import install_chaos

    conf = ConfArguments().parse(["--chaos", "bogus"])
    with pytest.raises(SystemExit):
        install_chaos(conf)
    assert faults.get_chaos() is None


def test_install_uninstall_roundtrip():
    inj = faults.install_chaos("fetch:error@1000")
    assert faults.get_chaos() is inj
    faults.perturb("fetch")  # rule armed but not firing: a no-op
    assert inj.calls("fetch") == 1
    faults.uninstall_chaos()
    assert faults.get_chaos() is None
    faults.perturb("fetch")  # uninstalled: free


# -- end-to-end: the guards under chaos --------------------------------------

def _write_replay(path, total, seed):

    statuses = list(
        SyntheticSource(total=total, seed=seed, base_ms=1785320000000).produce()
    )
    with open(path, "w") as fh:
        for s in statuses:
            fh.write(json.dumps(s.to_json()) + "\n")


CLOSED = "http://127.0.0.1:9"  # closed port: fails fast, no DNS


def test_chaos_smoke_linear_app_survives(tmp_path):
    """--chaos smoke (tier-1): the flagship app under fetch delays, an
    injected fetch error (the watchdog's re-issue path), dispatch delays,
    and a 100%-dead dashboard trains EVERY row — and the guard counters
    prove the faults actually fired and were absorbed."""
    import jax

    from twtml_tpu.apps import linear_regression as app

    jax.devices()  # lock the conftest's 8-device backend before local[1]
    path = tmp_path / "tweets.jsonl"
    _write_replay(path, 8 * 16, seed=31)

    conf = ConfArguments().parse([
        "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--master", "local[1]",
        "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
        "--chaos",
        "fetch:delay=0.02@3,fetch:error@7,step:delay=0.01@5,web:error,seed=1",
    ])
    totals = app.run(conf)
    assert totals["count"] == 8 * 16  # every row trained despite the chaos
    assert totals["batches"] == 8
    reg = _metrics.get_registry()
    assert reg.counter("chaos.injected").snapshot() > 0
    # the injected fetch error was absorbed by a re-issue, not an abort
    assert reg.counter("fetch.retries").snapshot() >= 1
    assert reg.counter("fetch.aborts").snapshot() == 0
    # the dead dashboard opened the breaker: failures capped at the
    # threshold, later publishes dropped without paying the timeout
    assert reg.gauge("publish.web.breaker_open").snapshot() == 1
    assert reg.counter("publish.web.failures").snapshot() >= 5
    assert reg.counter("publish.web.dropped").snapshot() >= 1


def test_chaos_wedged_fetch_aborts_with_checkpoint_then_resumes(
    tmp_path, monkeypatch
):
    """Acceptance: a fetch that stalls FOR GOOD (chaos ``from``-outage
    longer than deadline x retries) turns into a clean, checkpointed,
    non-zero-exit abort — and a restarted run RESUMES the learning curve
    from that checkpoint instead of starting over (today's alternative was
    a silent permanent hang in future.result())."""
    import jax

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    jax.devices()
    path = tmp_path / "tweets.jsonl"
    _write_replay(path, 8 * 16, seed=32)
    ck = str(tmp_path / "ck")

    base = [
        "--source", "replay", "--replayFile", str(path),
        "--seconds", "0", "--backend", "cpu",
        "--batchBucket", "16", "--tokenBucket", "64",
        "--master", "local[1]",
        "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
        "--checkpointDir", ck, "--checkpointEvery", "1",
    ]
    monkeypatch.setenv("TWTML_FETCH_DEADLINE_S", "0.2")
    monkeypatch.setenv("TWTML_FETCH_RETRIES", "1")
    with pytest.raises(RuntimeError, match="runtime guard"):
        app.run(ConfArguments().parse(
            base + ["--chaos", "fetch:delay=2@from4,seed=0"]
        ))
    assert _metrics.get_registry().counter("fetch.aborts").snapshot() == 1
    # the abort flushed a checkpoint at the last delivered batch
    state, meta = Checkpointer(ck).restore()
    assert meta["batches"] == 3
    assert meta["count"] == 3 * 16

    # restart WITHOUT chaos: counters (and weights) resume from the
    # checkpoint, the intake journal replays the rows the abort stranded
    # past the cursor, and the source fast-forwards past everything
    # journaled (ISSUE 19) — every row trains EXACTLY once, so the final
    # ledger equals an unfailed run over the file (the pre-journal
    # behavior re-read the whole file on top of the restored count)
    faults.uninstall_chaos()
    totals = app.run(ConfArguments().parse(list(base)))
    assert totals["batches"] == 8
    assert totals["count"] == 8 * 16
