"""The cell ``hash2e18-ab4-trimmed-280`` (PR 35: four hash-routed learners
on one stream, ``--tenants 4``): the flags it hands the program, what its
files share with ``hash2e18-trimmed-280``'s, and the faults its comparison
is there for, shown as ``test_logit2e18.py`` shows the labeler's:

1. a program that routes EVERY row to tenant 0 (one model trained on the
   whole stream, three left at zero): each batch still counts 2,048 rows, so
   ``count_diff`` stays 0 and ``weights_dev`` over the whole ``[M, F+4]``
   array turns ``correct`` false;
2. a tenant step that returns its state unchanged (``test_correct.py``'s
   BREAK_TRAIN for the plane's class alone; since PR 46 that patch breaks
   this class too, beside the single-model and the mesh classes, which this
   cell's timed path never enters).

Each run is ``run.py``'s own path at rehearsal sizes with the fault patched
in underneath; unbroken it is ``test_correct.py``'s case of this cell. Since
PR 46 the cell is off the rate's list (judged on ``batch_gap_ms_p95`` and
``setup_s``): ``test_the_cell_reports_the_planes_metrics_and_the_shared_ones``
pins its new sets.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hash2e18_ab4.py -q
"""

import os

import pytest

from benchmark import manifest
from benchmark.drivers import train
from benchmark.tests import test_contract
from benchmark.tests.test_correct import _drive

CELL = "hash2e18-ab4-trimmed-280"
FLAGS = test_contract.SHARED + test_contract.HASH2E18 + ["--tenants", "4"]

ROUTE_ALL_TO_TENANT_0 = """
import numpy as np
from twtml_tpu.parallel import tenants
tenants.tenant_route_keys = (
    lambda batch, m, mode="hash": np.zeros(batch.mask.shape[0], np.int32))
"""

BREAK_TENANT_STEP = """
import jax
from twtml_tpu.parallel import tenants
_step = tenants.TenantStackModel.step
def step(self, batch):
    w = jax.tree_util.tree_map(lambda a: a + 0, self._weights)  # donated below
    out = _step(self, batch)
    self._weights = w          # the state comes back unchanged
    return out
tenants.TenantStackModel.step = step
"""


@pytest.mark.parametrize("patch", [ROUTE_ALL_TO_TENANT_0, BREAK_TENANT_STEP])
def test_fault_turns_correct_false_by_the_weights(patch):
    got = _drive(CELL, patch)
    assert got["correct"] is False
    n = got["numbers"]["weights_dev"]
    assert n["value"] > n["limit"], n
    assert got["numbers"]["count_diff"]["value"] == 0


def test_program_flags_are_the_recorded_list():
    cell = manifest.cell(manifest.load(), CELL)
    assert train.program_flags(
        cell["config"], "tpu", "CKPT", "http://sink") == FLAGS


def test_the_cell_is_hash2e18_trimmed_280_with_the_plane_on():
    """Same mix (the file that stands, by name), same model but for the
    number of tenants and their key, same flags but for ``--tenants 4``,
    same rules of ``correct`` (``weights_dev``'s limit is the cell's own);
    the driver kind is ``train``: no file of the harness had to change."""
    cell = manifest.cell(manifest.load(), CELL)
    base = manifest.cell(manifest.load(), "hash2e18-trimmed-280")
    assert cell["traffic_path"] == base["traffic_path"]
    assert cell["traffic"]["kind"] == "train"
    cfg, was = cell["config"], base["config"]
    model = dict(cfg["model"])
    assert (model.pop("tenants"), model.pop("tenantKey")) == (4, "hash")
    assert model == was["model"]
    assert cfg["flags"] == was["flags"] + ["--tenants", "4"]
    assert cfg["app"] == was["app"] and cfg["batch_rows"] == was["batch_rows"]
    assert cfg["must_take_gram_plane"] is True
    assert cfg["reference"] == "benchmark/reference/tenant_linear_sgd.py"
    assert "statistic" not in cfg["correct"]        # half_up_integer
    limits, old = cfg["correct"]["limits"], was["correct"]["limits"]
    assert set(limits) == set(old)
    assert (limits["count_diff"], limits["mse_dev"]) == (
        old["count_diff"], old["mse_dev"])
    assert cell["config_entry"]["reduced"] == []
    assert os.path.isfile(manifest.work_count_path(cfg))


def test_the_cell_reports_the_planes_metrics_and_the_shared_ones():
    """Since PR 46 the cell is HOST-paced and off the rate's list
    (benchmark/README.md has the rule): it is judged on its tail and its
    set-up, reports the per-layer metrics that move those two and the pace
    itself (``host_round_ms_p50``), and is listed by NO metric that is, or
    ``moves``, ``ingest_tweets_per_s`` (the lint would refuse one). The
    plane's own metrics, and every metric that moves the rate, stay on
    ``hash2e18-lang4-trimmed-280``, which runs the same plane device-paced
    (``test_hash2e18_lang4.py``)."""
    m = manifest.load()
    cell = manifest.cell(m, CELL)
    assert [x["name"] for x in cell["end_to_end"]] == [
        "batch_gap_ms_p95", "setup_s"]
    moves = {x["name"]: x["moves"] for x in cell["per_layer"]}
    assert set(moves) >= {
        "compiles_in_window", "fetch_ms_per_batch", "publish_ms_per_batch",
        "deliver_wait_ms_per_batch", "warmup_compile_s",
        "paired_delivery_share", "publish_reuse_share", "host_round_ms_p50"}
    assert moves.pop("warmup_compile_s") == "setup_s"
    assert set(moves.values()) <= {"batch_gap_ms_p95", "setup_s"}
    assert not [x["name"] for x in m["end_to_end"] + m["per_layer"]
                if "ingest_tweets_per_s" in (x["name"], x.get("moves"))
                and CELL in x["workloads"]]


def test_readers_find_nothing_in_a_program_without_the_plane(
        tmp_path, monkeypatch):
    """The parent's program, and every single-model cell, has neither the
    span nor the instant: the readers return None and raise nothing."""
    from benchmark import trace_files

    split = manifest.load_module(
        manifest.layer_metric_path("tenant_split_ms_per_batch"))
    assert split.read({}) is None
    assert split.read({"spans": {"wire_pack": {"count": 3, "total_ms": 1}}}
                      ) is None
    assert split.read({"spans": {"tenant_split": {
        "count": 4, "total_ms": 10.0}}}) == 2.5
    pad = manifest.load_module(manifest.layer_metric_path("tenant_pad_share"))
    monkeypatch.setattr(trace_files, "span_file", lambda: None)
    assert pad.read({}) is None      # no live traced run: no span file
    path = tmp_path / "spans.json"

    def write(events):
        test_contract.write_spans(path, events)

    monkeypatch.setattr(trace_files, "span_file", lambda: str(path))
    write([{"name": "gram_plane", "ph": "i", "args": {"plane": 1}}])
    assert pad.read({}) is None      # a program without the instant
    write([{"name": "tenant_rows", "ph": "i", "args": {
        "batch": b, "rows": [500, 520, 530, 498], "pad_rows": 6144}}
        for b in range(3)])
    assert pad.read({}) == 75.0
