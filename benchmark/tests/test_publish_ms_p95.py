"""``publish_ms_p95`` (PR 53): a per-layer metric APPENDED for all eight
cells, so it is now the last of every cell's list — and two cases of
``test_hash2e20_grid4.py`` hold a cell's own metrics to the END of its list
(``mine[-2:] == ADDED``, ``mine[-3:] == grid4.ADDED``). This PR may not edit
that file, so tier-1 imports the two cases below in their place
(tests/test_benchmark_contract.py), AS THEY NOW READ: a cell's own metrics
stand in the order they were added, whatever a later PR appends for every
cell after them. Everything else they assert is asserted here, word for word;
PERF.md section 7 has the line for the next ``benchmark`` issue to restate the
old ones. The reader itself, on a span file made by hand, is
``tests/test_web.py::test_publish_ms_p95_reads_the_spans_durations``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_publish_ms_p95.py -q
"""

from benchmark import manifest
from benchmark.tests import test_hash2e18_grid4 as grid4
from benchmark.tests.test_hash2e20_grid4 import (
    ADDED, ARM_READERS, CELL, GRID, MESH)

NAME = "publish_ms_p95"


def test_the_metric_is_the_last_entry_and_every_cell_lists_it():
    loaded = manifest.load()
    entry = loaded["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "publish",
        "moves": "batch_gap_ms_p95",
        "workloads": [w["name"] for w in loaded["workloads"]]}
    for w in loaded["workloads"]:
        cell = manifest.cell(loaded, w["name"])
        assert cell["per_layer"][-1]["name"] == NAME
    assert manifest.lint() == []


def test_the_cell_reports_hash2e20s_metrics_the_arms_two_and_its_own_two():
    """``test_hash2e20_grid4.py``'s case of this name, as it now reads."""
    cell = manifest.cell(manifest.load(), CELL)
    mesh = manifest.cell(manifest.load(), MESH)
    mine = [m["name"] for m in cell["per_layer"]]
    assert [m for m in mine if m not in ADDED + ARM_READERS] == [
        m["name"] for m in mesh["per_layer"]]
    assert not [m for m in mine if m.startswith("tenant_")]
    assert "arm_contraction_hbm_share" not in mine
    assert [m for m in mine if m in ARM_READERS + ADDED] == (
        ARM_READERS + ADDED)
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in mesh["end_to_end"]]
    by_name = {m["name"]: m for m in cell["per_layer"]}
    for name in ADDED:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["moves"], m["source"]) == (
            "device_step", "ingest_tweets_per_s", "device_trace")
    for name in ARM_READERS:
        assert by_name[name]["workloads"] == [GRID, CELL]


def test_grid4_reports_the_single_models_metrics_and_its_own_three():
    """``test_hash2e20_grid4.py``'s case of this name (itself
    ``test_hash2e18_grid4.py``'s, restated by PR 52), as it now reads."""
    cell = manifest.cell(manifest.load(), GRID)
    single = manifest.cell(manifest.load(), grid4.SINGLE)
    lang = manifest.cell(manifest.load(), grid4.LANG)
    mine = [m["name"] for m in cell["per_layer"]]
    assert [m for m in mine if m not in grid4.ADDED] == [
        m["name"] for m in single["per_layer"]]
    assert [m for m in mine if m not in grid4.ADDED] == [
        m["name"] for m in lang["per_layer"]
        if not m["name"].startswith("tenant_")]
    assert [m for m in mine if m in grid4.ADDED] == grid4.ADDED
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in single["end_to_end"]]
    for m in cell["per_layer"]:
        if m["name"] in grid4.ADDED:
            assert m["workloads"] == (
                [GRID, CELL] if m["name"] in ARM_READERS else [GRID])
            assert (m["layer"], m["moves"], m["source"]) == (
                "device_step", "ingest_tweets_per_s", "device_trace")
