"""The cell ``hash2e18-lang4-trimmed-280`` (PR 42: four SCRIPT-routed
learners on one stream, ``--tenants 4 --tenantKey lang``): the flags it
hands the program (recorded here and, since PR 46, in
``test_contract.FLAGS``), what its files share with
``hash2e18-ab4-trimmed-280``'s, its readers on a span file worked by hand,
and the faults its comparison is there for, shown as ``test_hash2e18_ab4.py``
shows its cell's:

1. a program that routes by the HASH key in the ``lang`` key's place (four
   other models on the same rows);
2. a program that routes EVERY row to tenant 0;
3. a tenant step that returns its state unchanged.

In all three every batch still counts 2,048 rows, so ``count_diff`` stays 0
and ``weights_dev`` over the whole ``[M, F+4]`` array turns ``correct``
false. Each run is ``run.py``'s own path at rehearsal sizes with the fault
patched in underneath; unbroken it is ``test_correct.py``'s case of this
cell (whose ``BREAK_TRAIN`` breaks the plane's class too since PR 46:
PERF.md section 7 row 15).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hash2e18_lang4.py -q
"""

import os

import pytest

from benchmark import manifest, trace_files
from benchmark.drivers import train
from benchmark.tests import test_contract
from benchmark.tests.test_correct import _drive
from benchmark.tests.test_hash2e18_ab4 import (
    BREAK_TENANT_STEP,
    ROUTE_ALL_TO_TENANT_0,
)

CELL = "hash2e18-lang4-trimmed-280"
CONTROL = "hash2e18-ab4-trimmed-280"
FLAGS = test_contract.SHARED + test_contract.HASH2E18 + [
    "--tenants", "4", "--tenantKey", "lang"]
ADDED = ["tenant_need_share", "tenant_fullest_share", "tenant_s8_part_share"]
PLANE = ["tenant_split_ms_per_batch", "tenant_pad_share"]   # PR 35's two

ROUTE_BY_THE_HASH_KEY = """
from twtml_tpu.parallel import tenants
_route = tenants.tenant_route_keys
tenants.tenant_route_keys = lambda batch, m, mode="hash": _route(batch, m, "hash")
"""


@pytest.mark.parametrize("patch", [
    ROUTE_BY_THE_HASH_KEY, ROUTE_ALL_TO_TENANT_0, BREAK_TENANT_STEP])
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


def test_the_cell_is_the_ab4_cell_with_the_other_key():
    """Same mix (the file that stands, by name), same model but for the key,
    same flags but for ``--tenantKey lang``, ``ab4``'s work count BY NAME
    (no copy), same rules of ``correct`` (``weights_dev``'s limit is the
    cell's own, from its own readings), a reference of its own that states
    the ``lang`` rule; the driver kind is ``train``: no file of the harness
    had to change."""
    cell = manifest.cell(manifest.load(), CELL)
    base = manifest.cell(manifest.load(), CONTROL)
    assert cell["traffic_path"] == base["traffic_path"]
    assert cell["traffic"]["kind"] == "train"
    cfg, was = cell["config"], base["config"]
    assert dict(cfg["model"], tenantKey="hash") == was["model"]
    assert cfg["model"]["tenantKey"] == "lang"
    assert cfg["flags"] == was["flags"] + ["--tenantKey", "lang"]
    assert cfg["app"] == was["app"] and cfg["batch_rows"] == was["batch_rows"]
    assert cfg["must_take_gram_plane"] is True
    assert cfg["reference"] == "benchmark/reference/tenant_lang_linear_sgd.py"
    assert manifest.work_count_path(cfg) == manifest.work_count_path(was)
    assert os.path.isfile(manifest.work_count_path(cfg))
    assert "statistic" not in cfg["correct"]        # half_up_integer
    limits, old = cfg["correct"]["limits"], was["correct"]["limits"]
    assert set(limits) == set(old)
    assert (limits["count_diff"], limits["mse_dev"]) == (
        old["count_diff"], old["mse_dev"])
    assert cell["config_entry"]["reduced"] == []
    assert cell["workload"]["chips"] == 1


def test_the_cell_reports_ab4s_metrics_and_its_own_three():
    """Re-stated in PR 46, when the control left the rate's list and with it
    every per-layer metric that moves the rate: this cell reports what the
    single-model cell on the same mix reports (``hash2e18-trimmed-280``),
    the PLANE's two (which ``ab4`` reported until then), and its own three;
    every metric ``ab4`` still reports is among them. The three are listed
    on THIS cell alone (PERF.md section 7 row 20)."""
    cell = manifest.cell(manifest.load(), CELL)
    single = manifest.cell(manifest.load(), "hash2e18-trimmed-280")
    control = manifest.cell(manifest.load(), CONTROL)
    mine = [m["name"] for m in cell["per_layer"]]
    shared = [m["name"] for m in single["per_layer"]]
    assert [m for m in mine if m not in ADDED + PLANE] == shared
    assert sorted(set(mine) - set(shared)) == sorted(ADDED + PLANE)
    assert {m["name"] for m in control["per_layer"]} < set(mine)
    assert not {m["name"] for m in control["per_layer"]} & set(ADDED + PLANE)
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in single["end_to_end"]]
    for m in cell["per_layer"]:
        if m["name"] in ADDED:
            assert m["workloads"] == [CELL]
        if m["name"] in ADDED + PLANE:
            assert m["moves"] == "ingest_tweets_per_s"


def test_readers_on_a_span_file_worked_by_hand(tmp_path, monkeypatch):
    """No live traced run, a program without the instant (a single-model
    cell's) and the PARENT's instant (``rows``, ``bucket``, ``pad_rows``; no
    ``planes``): the readers return None where there is nothing to read and
    raise nothing. Then two batches by hand."""
    need, fullest, s8 = (
        manifest.load_module(manifest.layer_metric_path(n)) for n in ADDED)
    monkeypatch.setattr(trace_files, "span_file", lambda: None)
    assert [r.read({}) for r in (need, fullest, s8)] == [None] * 3
    path = tmp_path / "spans.json"

    def write(events):
        test_contract.write_spans(path, events)

    monkeypatch.setattr(trace_files, "span_file", lambda: str(path))
    write([{"name": "gram_plane", "ph": "i", "args": {"plane": 1}}])
    assert [r.read({}) for r in (need, fullest, s8)] == [None] * 3

    def rows(counts, bucket, **more):
        return {"name": "tenant_rows", "ph": "i", "args": dict(
            batch=0, rows=counts, bucket=bucket,
            pad_rows=4 * bucket - sum(counts), **more)}

    # the parent's instants: an even split at the first rung of 2,048 rows
    write([rows([512, 512, 512, 512], 640)] * 3)
    assert need.read({}) == 100.0 * 4 * 512 ** 2 / (4 * 640 ** 2)   # 64.0
    assert fullest.read({}) == 25.0
    assert s8.read({}) is None
    # from before PR 36 the instant had no bucket: nothing to divide by
    write([{"name": "tenant_rows", "ph": "i", "args": {
        "rows": [500, 520, 530, 498], "pad_rows": 6144}}])
    assert need.read({}) is None
    assert fullest.read({}) == 100.0 * 530 / 2048
    # this cell's: the top rung, one batch with a dry tenant on s8
    write([rows([1536, 256, 0, 256], 2048, planes=[1, 1, 2, 1]),
           rows([1024, 512, 256, 256], 2048, planes=[1, 1, 1, 1])])
    spent = 2 * 4 * 2048 ** 2
    want = (1536 ** 2 + 2 * 256 ** 2) + (1024 ** 2 + 512 ** 2 + 2 * 256 ** 2)
    assert need.read({}) == 100.0 * want / spent                    # 11.72
    assert fullest.read({}) == 100.0 * (0.75 + 0.5) / 2             # 62.5
    assert s8.read({}) == 12.5                                      # 1 of 8
