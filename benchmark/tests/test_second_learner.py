"""The proof that the training driver takes its learner as DATA (PR 31): a
second learner — ``apps/logistic_regression``, its own plain reference
(``fixtures/logistic_ref.py``), the ``rate`` rule for its printed statistic
and labels read from lexicon words in the text — runs through ``run.py``'s
own code path from a tree that only ADDS files (``fixture_tree.build``):
``correct`` true; with the bf16 control in the program's place, false; with
a train step that returns its state unchanged, false. No cell and no
configuration of the benchmark: nothing here is named in ``BENCHMARK.json``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_second_learner.py -q
"""

import json
import os

import numpy as np
import pytest

from benchmark import gen, manifest
from benchmark.tests import fixture_tree
from benchmark.tests.fixtures import logistic_ref
from benchmark.tests.test_correct import BREAK_TRAIN

REHEARSE = ["--seed", "2147483659", "--seconds", "2", "--trace", "0",
            "--rehearse"]


@pytest.fixture(scope="module")
def tree():
    path = fixture_tree.build(os.path.join(
        manifest.ROOT, "_scratch", "second_learner"))
    yield path
    fixture_tree.remove(path)


def _result(tree, argv, prelude=""):
    p = fixture_tree.run(tree, argv, prelude, timeout=900,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


@pytest.mark.parametrize("prelude,want", [("", True), (BREAK_TRAIN, False)])
def test_second_learner_through_run_py(tree, prelude, want):
    got, log = _result(tree, REHEARSE, prelude)
    assert got["correct"] is want and got["rehearsal"] is True
    # held by its own rule and its own reference, in both runs of the entry
    assert "correct: rate_dev = " in log and "window_rate_dev" in log
    assert "mse_dev" not in log
    if not want:   # the weights did not move: the reference's did
        assert "weights_dev = 1 (" in log


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_its_control_is_not_correct(tree, seed):
    """Pure NumPy, at the fixture's full size (2^18 dims, batches of 2048):
    ``control.py`` got the fixture's reference in both precisions with no
    change of its own."""
    got, _log = _result(tree, ["--seed", str(seed), "--seconds", "1",
                               "--control", "bf16"])
    assert got["correct"] is False
    dev = got["numbers"]["weights_dev"]
    assert dev["value"] > 3 * dev["limit"]
    assert "rate_dev" in got["numbers"] and "mse_dev" not in got["numbers"]


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_every_check_batch_holds_both_labels(seed):
    """At the fixture's own batch of 2048: the share of tweets labelled 0
    (negative lexicon words outnumber positive) lies between 5% and 30% in
    each of the four check batches. Without the lexicon it is 0."""
    g = manifest.load_json(manifest.traffic_path("trimmed-kept-280"))["generator"]
    g["lexicon"] = manifest.load_json(
        os.path.join(fixture_tree.FIXTURES, "lexicon.json"))["lexicon"]
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0, 4 * 2048)
    assert chunk.kept.all()
    shares = [float(np.mean(logistic_ref.labels_of(
        chunk.text[b * 2048:(b + 1) * 2048], g["lexicon"]) == 0.0))
        for b in range(4)]
    print(f"label-0 share of the four check batches, seed {seed}: {shares}")
    assert all(0.05 <= s <= 0.30 for s in shares), shares
