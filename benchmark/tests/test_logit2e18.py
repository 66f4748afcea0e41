"""The faults ``rate_dev`` is there for, shown on the cell
``logit2e18-trimmed-280-lex`` (PR 32): on the chip the sound runs AND the
bf16 control read ``rate_dev`` 0 (a lower precision moves weights, not a
hard 0/1 class of a row with a margin), so what turns it is a program that
classes or labels OTHER ROWS than the reference:

1. a labeler that answers 1.0 for every row (the label is read from the
   text on the host: a stream that labels every tweet alike trains nothing);
2. half of every batch left out (its mask cleared before the step).

Each run is ``run.py``'s own path at rehearsal sizes with the fault patched
in underneath; unbroken it is ``test_correct.py``'s case of this cell.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_logit2e18.py -q
"""

import os

import pytest

from benchmark import manifest
from benchmark.drivers import train
from benchmark.tests import test_contract
from benchmark.tests.test_correct import _drive

CELL = "logit2e18-trimmed-280-lex"

LABEL_ALL_ONES = """
import numpy as np
from twtml_tpu.apps import logistic_regression as app
app.sentiment_labels_from_units = (
    lambda units, offsets: np.ones(offsets.size - 1, np.float32))
"""

HALF_THE_BATCH = """
from twtml_tpu.features.featurizer import Featurizer
_whole = Featurizer.featurize_parsed_block
def half(self, block, *a, **kw):
    batch = _whole(self, block, *a, **kw)
    batch.mask[block.rows // 2:] = 0.0
    return batch
Featurizer.featurize_parsed_block = half
"""


@pytest.mark.parametrize("patch,also", [
    (LABEL_ALL_ONES, ()),
    (HALF_THE_BATCH, ("count_diff",)),
])
def test_fault_turns_correct_false_by_the_rate(patch, also):
    got = _drive(CELL, patch)
    assert got["correct"] is False
    for name in ("rate_dev", *also):
        n = got["numbers"][name]
        assert n["value"] > n["limit"], (name, n)


def test_the_cell_is_the_fixtures_learner_on_its_own_files():
    """The configuration and the mix that stand are the fixture's (which
    stays, to prove addition by files alone) but for what a deployment
    states: the reference's place, the mesh flag, what was assumed, the
    limits read on the chip."""
    cfg = manifest.load_json(os.path.join(
        manifest.HERE, "configs", "logit2e18.json"))
    fix = manifest.load_json(os.path.join(
        manifest.HERE, "tests", "fixtures", "logit2e18.json"))
    assert cfg["model"] == fix["model"] and cfg["app"] == fix["app"]
    assert cfg["flags"] == fix["flags"] + ["--master", "local[1]"]
    assert cfg["reference"] == "benchmark/reference/logistic_sgd.py"
    assert cfg["correct"]["statistic"] == "rate"
    mix = manifest.load_json(manifest.traffic_path("trimmed-kept-280-lex"))
    base = manifest.load_json(manifest.traffic_path("trimmed-kept-280"))
    lex = mix["generator"].pop("lexicon")
    assert mix["generator"] == base["generator"]
    assert lex == manifest.load_json(os.path.join(
        manifest.HERE, "tests", "fixtures", "lexicon.json"))["lexicon"]


def test_program_flags_are_the_recorded_list():
    cell = manifest.cell(manifest.load(), CELL)
    assert train.program_flags(
        cell["config"], "tpu", "CKPT", "http://sink") == test_contract.FLAGS[CELL]


# -- the gate of drivers/train_text_label.py ---------------------------------

def _labeler_of_the_parent(units, offsets):
    """The block labeler as it stood before PR 32: the C scan's score for
    rows of ASCII units, the per-row Python rule for every other row."""
    import numpy as np

    from twtml_tpu.features import native, sentiment

    n = offsets.size - 1
    score = native.lexicon_scores(
        (units, offsets), n, sentiment._POS_PACKED, sentiment._NEG_PACKED)
    labels = (score >= 0).astype(np.float32)
    for i in range(n):
        row = units[offsets[i]:offsets[i + 1]]
        if (row >= 128).any():
            text = row.tobytes().decode("utf-16-le", "surrogatepass")
            labels[i] = 1.0 if sentiment.sentiment_score(text) >= 0 else 0.0
    return labels


def test_the_mix_names_the_gated_driver():
    mix = manifest.load_json(manifest.traffic_path("trimmed-kept-280-lex"))
    assert mix["kind"] == "train_text_label"
    assert os.path.isfile(manifest.driver_path(mix["kind"]))


def test_gate_counts_no_row_on_this_program():
    from benchmark.drivers import train_text_label as gated

    assert gated.rows_through_the_python_rule() == 0


@pytest.mark.parametrize("labeler,rows", [
    (_labeler_of_the_parent, 3),       # each row holding a unit >= 128
    (None, 4),                         # no C library: every row
])
def test_gate_refuses_a_labeler_that_falls_back(monkeypatch, labeler, rows):
    from benchmark.drivers import train_text_label as gated
    from twtml_tpu.features import native, sentiment

    if labeler is None:
        monkeypatch.setattr(native, "lexicon_scores", lambda *a: None)
    else:
        monkeypatch.setattr(sentiment, "sentiment_labels_from_units", labeler)
    assert gated.rows_through_the_python_rule() == rows
    started = []
    monkeypatch.setattr(gated.harness, "place_compile_cache", lambda: "")
    monkeypatch.setattr(gated.train, "run", lambda *a: started.append(a))
    with pytest.raises(SystemExit) as stop:
        gated.run(manifest.cell(manifest.load(), CELL), None, 0.0)
    assert "refusing to measure the fallback" in str(stop.value.code)
    assert not started


def test_gate_hands_over_to_train_unchanged(monkeypatch):
    from benchmark.drivers import train_text_label as gated

    monkeypatch.setattr(gated.train, "run", lambda *a: {"got": a})
    # the compile cache is placed BEFORE the probe imports the program, and
    # jax with it (PR 34: placed after, this cell alone never found its cache)
    order = []
    probe = gated.rows_through_the_python_rule
    monkeypatch.setattr(gated.harness, "place_compile_cache",
                        lambda: order.append("cache"))
    monkeypatch.setattr(gated, "rows_through_the_python_rule",
                        lambda: order.append("probe") or probe())
    cell = manifest.cell(manifest.load(), CELL)
    assert gated.run(cell, "ARGS", 1.5) == {"got": (cell, "ARGS", 1.5)}
    assert order == ["cache", "probe"]
