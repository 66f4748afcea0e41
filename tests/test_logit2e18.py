"""The deployment ``logit2e18`` (BASELINE.json configs[2]: MLlib 1.6.1
``StreamingLogisticRegressionWithSGD``, no-argument constructor) through its
NORMAL path at a small size on the CPU, against its plain reference
(``benchmark/reference/logistic_sgd.py``: NumPy, float64): one run of
``apps/logistic_regression.run`` — block ingest, ragged wire, Gram basis,
verified checkpoint, ``--trace`` — on a generated pool that carries lexicon
words, whose label the program reads from the TEXT on the host.
"""

import contextlib
import io

import numpy as np
import pytest

from benchmark import compare, gen, manifest, spans
from benchmark.reference import logistic_sgd

CLOSED = "http://127.0.0.1:9"
ROWS, BATCHES, F_TEXT, SEED = 64, 4, 16384, 3200000171
MODEL = {"numTextFeatures": F_TEXT, "numIterations": 50, "stepSize": 0.1}

# Σ|w − w_ref| ÷ Σ|w_ref| after the four batches. The program keeps float32
# weights, features and accumulators (the configuration's stated precision);
# against the float64 reference that reads 1.7e-7 here on the CPU backend (and
# 2.9–3.3e-7 on the chip at the full size: PERF.md section 2). The bf16
# control — every product's floating operands rounded to bfloat16, the
# nearest precision below — reads 2.0e-4 at this size. 2e-5 sits two decades
# over the sound reading and one under the control's.
WEIGHTS_TOL = 2e-5
RATE_TOL = 1e-4   # a tenth of a row of 2048; the rule's own allowances aside


def _generator():
    g = manifest.load_json(
        manifest.traffic_path("trimmed-kept-280-lex"))["generator"]
    return dict(g, pool_lines=ROWS * BATCHES)


@pytest.fixture(scope="module")
def run_record(tmp_path_factory):
    """ONE run of the entry point; the tests below read its record."""
    from twtml_tpu.apps import logistic_regression as app
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.serving import load_servable
    from twtml_tpu.telemetry import metrics

    tmp = tmp_path_factory.mktemp("logit2e18")
    g = _generator()
    chunk = gen.make_chunk(g, gen.build_vocab(g, SEED), SEED, 0, ROWS * BATCHES)
    assert chunk.kept.all()
    replay = tmp / "pool.jsonl"
    replay.write_text("\n".join(chunk.lines) + "\n", encoding="utf-8")
    trace_path, ckpt = str(tmp / "spans.json"), str(tmp / "ckpt")
    conf = ConfArguments().parse([
        "--backend", "cpu", "--master", "local[1]", "--source", "replay",
        "--replayFile", str(replay), "--ingest", "block", "--seconds", "0",
        "--numTextFeatures", str(F_TEXT), "--stepSize", "0.1",
        "--batchBucket", str(ROWS), "--checkpointDir", ckpt,
        "--twtweb", CLOSED, "--lightning", CLOSED, "--trace", trace_path,
    ])
    assert conf.effective_wire() == "ragged"
    metrics.reset_for_tests()
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setenv("TWTML_NOW_MS", str(g["now_ms"]))
        totals = app.run(conf, max_batches=BATCHES)
    reg = metrics.get_registry()
    gauges = {k: reg.gauge(k).snapshot() for k in (
        "model.gram_plane", "model.label0_share", "featurize.label_ms")}
    snapshot, reason = load_servable(ckpt)
    assert snapshot is not None, reason
    lines = [l.split() for l in out.getvalue().splitlines()
             if l.startswith("count: ")]
    ref, ref_stats = logistic_sgd.train_on_chunks(
        [chunk], batch_rows=ROWS, n_batches=BATCHES, model=MODEL, generator=g)
    control, _ = logistic_sgd.train_on_chunks(
        [chunk], batch_rows=ROWS, n_batches=BATCHES, model=MODEL, generator=g,
        precision="bf16")
    return {
        "totals": totals, "weights": np.asarray(snapshot.weights, np.float64),
        "batches": [{"count": int(f[1]), "batch": int(f[3]),
                     "stat": float(f[5])} for f in lines],
        "gauges": gauges, "events": spans.load_events(trace_path),
        "ref": ref.w, "ref_stats": ref_stats, "control": control.w,
    }


def _weights_dev(w, ref):
    return float(np.sum(np.abs(w - ref)) / np.sum(np.abs(ref)))


def test_weights_and_rates_match_the_plain_reference(run_record):
    r = run_record
    assert r["totals"]["batches"] == BATCHES
    assert r["totals"]["count"] == ROWS * BATCHES
    assert [b["batch"] for b in r["batches"]] == [ROWS] * BATCHES
    rule = compare.STATISTICS["rate"]
    for got, ref in zip(r["batches"], r["ref_stats"]):
        assert rule.deviation(got["stat"], ref) <= RATE_TOL, (got, ref)
    # both labels in every batch (else nothing is learnt), and the learner
    # moved: after batch 1 the rate is no longer the share of label 1
    shares = [s["label0_share"] for s in r["ref_stats"]]
    assert all(0.0 < s < 0.5 for s in shares), shares
    assert r["batches"][0]["stat"] == pytest.approx(1 - shares[0], abs=6e-4)
    assert r["batches"][-1]["stat"] < 0.5
    assert _weights_dev(r["weights"], r["ref"]) < WEIGHTS_TOL


def test_the_bf16_control_fails_the_same_tolerance(run_record):
    assert _weights_dev(run_record["control"], run_record["ref"]) > 3 * WEIGHTS_TOL


def test_gram_basis_engaged_and_label_share_published(run_record):
    g = run_record["gauges"]
    assert g["model.gram_plane"] >= 0      # -1 = no Gram basis
    last = run_record["ref_stats"][-1]["label0_share"]
    assert g["model.label0_share"] == pytest.approx(last, abs=1e-4)
    assert g["featurize.label_ms"] > 0


def test_label_span_is_its_own_substage_under_trace(run_record):
    ev = [e for e in run_record["events"] if e.get("ph") == "X"]
    label = [e for e in ev if e["name"] == "featurize.label"]
    assert len(label) == BATCHES
    for e in label:
        assert e["args"]["rows"] == ROWS
        # 20-280 UTF-16 units a row, two bytes a unit (30% non-ASCII rows:
        # the wide wire)
        assert 2 * 20 * ROWS <= e["args"]["bytes"] <= 2 * 280 * ROWS
    # taken OUT of featurize.numeric: the two never overlap
    for lab, num in zip(label, [e for e in ev
                                if e["name"] == "featurize.numeric"]):
        assert (lab["ts"] >= num["ts"] + num["dur"] - 0.2
                or num["ts"] >= lab["ts"] + lab["dur"] - 0.2)
    # the C scan labelled every row: nothing fell back
    assert not [e for e in ev if e["name"] == "label_fallback"]
    st = spans.summarize(run_record["events"], 0.0, 1e12)
    from benchmark.layer_metrics import label_fallback_share, label_us_per_tweet

    art = {"spans": st, "tweets": ROWS * BATCHES}
    assert label_fallback_share.read(art) == 0.0
    assert label_us_per_tweet.read(art) == pytest.approx(
        1e3 * sum(e["dur"] for e in label) / 1e3 / (ROWS * BATCHES))
    # a program without the span (the parent's): nothing to read, no raise
    assert label_us_per_tweet.read({"spans": {}, "tweets": 10}) is None
    assert label_fallback_share.read({"spans": {"featurize": {}}}) is None
