"""``gram_fast_plane_share`` on three span files recorded from the trainer
(``--trace``, four batches of 16 rows at 16,384 dims on the CPU backend;
``testdata/gram_plane/<case>/cell/spans.json``, laid out as a live cell's
work directory): ``fast`` — every batch on a fast plane; ``mixed`` —
batches 2 and 4 hold one 280-unit text of a single repeated bigram (count
279 on one feature), which needs the exact plane; ``before`` — the same
run by the program of PR 24, which writes no ``gram_plane`` instant."""

import os

import pytest

from benchmark import harness, manifest

DATA = os.path.join(manifest.HERE, "testdata", "gram_plane")


@pytest.mark.parametrize("case, want", [
    ("fast", 100.0), ("mixed", 50.0), ("before", None), ("no_run", None),
])
def test_share_of_batches_on_a_fast_plane(case, want, monkeypatch):
    monkeypatch.setattr(harness, "WORK", os.path.join(DATA, case))
    read = manifest.load_module(
        manifest.layer_metric_path("gram_fast_plane_share")).read
    assert read({}) == want
