"""The cell ``hash2e18-grid4-trimmed-280`` (PR 47: a champion and three
challengers on the SAME rows, ``--tenants 4 --tenantKey all`` with a recipe
an arm): the flags it hands the program (recorded here until a ``benchmark``
PR moves them into ``test_contract.FLAGS``), what its files share with the
cells it reads against, its three readers on a trace made by hand, and the
faults its comparison is there for, shown as ``test_hash2e18_lang4.py``
shows its cell's:

1. every arm given arm 0's recipe (four copies of the champion);
2. arms 1 and 2 swapped (the right models in the wrong rows of the stack);
3. the HASH key in ``all``'s place (each recipe trained on a quarter of the
   rows);
4. a step that returns its state unchanged.

In all four every batch still counts 2,048 rows, so ``count_diff`` stays 0
and ``weights_dev`` over the whole ``[4, F+4]`` array turns ``correct``
false. Each run is ``run.py``'s own path at rehearsal sizes with the fault
patched in underneath (a minute each: run by hand; their in-process twins
are in ``tests/test_tenant_grid.py``); unbroken it is ``test_correct.py``'s
case of this cell.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hash2e18_grid4.py -q
"""

import os

import pytest

from benchmark import harness, manifest, trace_files
from benchmark.drivers import train
from benchmark.tests import test_contract
from benchmark.tests.test_correct import _drive
from benchmark.tests.test_hash2e18_ab4 import BREAK_TENANT_STEP

CELL = "hash2e18-grid4-trimmed-280"
SINGLE = "hash2e18-trimmed-280"      # M = 1, same mix
LANG = "hash2e18-lang4-trimmed-280"  # the whole step mapped over M = 4
FLAGS = test_contract.SHARED + test_contract.HASH2E18 + [
    "--tenants", "4", "--tenantKey", "all",
    "--tenantStepSize", "0.005,0.005,0.0025,0.0025",
    "--tenantL2Reg", "0.1,0.01,0.1,0.01"]
ADDED = ["arm_apply_ms_per_arm", "arm_shared_ms_per_batch",
         "arm_contraction_hbm_share"]

_RECIPES = """
from twtml_tpu.config import ConfArguments
ConfArguments.tenant_recipes = lambda self: (%s, %s)
"""
EVERY_ARM_THE_CHAMPION = _RECIPES % ("[0.005] * 4", "[0.1] * 4")
ARMS_1_AND_2_SWAPPED = _RECIPES % (
    "[0.005, 0.0025, 0.005, 0.0025]", "[0.1, 0.1, 0.01, 0.01]")
THE_HASH_KEY_IN_ALLS_PLACE = """
from twtml_tpu.parallel.tenants import TenantStackModel
_from_conf = TenantStackModel.from_conf.__func__
TenantStackModel.from_conf = classmethod(
    lambda cls, conf, mesh=None, **kw: _from_conf(
        cls, conf, mesh, **dict(kw, tenant_key="hash")))
"""


@pytest.mark.parametrize("patch", [
    EVERY_ARM_THE_CHAMPION, ARMS_1_AND_2_SWAPPED, THE_HASH_KEY_IN_ALLS_PLACE,
    BREAK_TENANT_STEP])
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


def test_the_cell_is_hash2e18_trimmed_280_with_four_recipes_on_its_rows():
    """Same mix as the single-model cell and both tenant cells (the file
    that stands, by name), ``hash2e18``'s model and flags with the plane's
    four keys added, ``hash2e18``'s work count BY NAME (ONE Gram a batch is
    what the deployment needs, whatever M), arm 0 = ``hash2e18`` itself, a
    reference of its own; the driver kind is ``train``: no file of the
    harness had to change."""
    cell = manifest.cell(manifest.load(), CELL)
    base = manifest.cell(manifest.load(), SINGLE)
    lang = manifest.cell(manifest.load(), LANG)
    assert cell["traffic_path"] == base["traffic_path"] == lang["traffic_path"]
    assert cell["traffic"]["kind"] == "train"
    cfg, was = cell["config"], base["config"]
    model = dict(cfg["model"])
    steps, l2s = model.pop("tenantStepSize"), model.pop("tenantL2Reg")
    assert (model.pop("tenants"), model.pop("tenantKey")) == (4, "all")
    assert model == was["model"]
    assert (steps[0], l2s[0]) == (was["model"]["stepSize"],
                                  was["model"]["l2Reg"])
    assert sorted(set(zip(steps, l2s))) == sorted(
        (s, r) for s in (0.005, 0.0025) for r in (0.1, 0.01))
    assert cfg["flags"] == was["flags"] + FLAGS[-8:]
    assert cfg["flags"][-3] == ",".join(str(s) for s in steps)
    assert cfg["flags"][-1] == ",".join(str(r) for r in l2s)
    assert cfg["app"] == was["app"] and cfg["batch_rows"] == was["batch_rows"]
    assert cfg["must_take_gram_plane"] is True
    assert cfg["reference"] == "benchmark/reference/grid_linear_sgd.py"
    assert manifest.work_count_path(cfg) == manifest.work_count_path(was)
    assert os.path.isfile(manifest.work_count_path(cfg))
    assert "statistic" not in cfg["correct"]        # half_up_integer
    limits, old = cfg["correct"]["limits"], was["correct"]["limits"]
    assert set(limits) == set(old)
    assert (limits["count_diff"], limits["mse_dev"]) == (
        old["count_diff"], old["mse_dev"])
    assert cell["config_entry"]["reduced"] == []
    assert cell["workload"]["chips"] == 1


def test_the_cell_reports_the_single_models_metrics_and_its_own_three():
    """No split, no padding, no skew here: none of the five ``tenant_*``
    metrics; everything else ``hash2e18-lang4-trimmed-280`` reports, which
    is what the single-model cell on the same mix reports; and the three
    ``arm_*``, listed on THIS cell alone, on the layer ``device_step``,
    moving the rate."""
    cell = manifest.cell(manifest.load(), CELL)
    single = manifest.cell(manifest.load(), SINGLE)
    lang = manifest.cell(manifest.load(), LANG)
    mine = [m["name"] for m in cell["per_layer"]]
    assert [m for m in mine if m not in ADDED] == [
        m["name"] for m in single["per_layer"]]
    assert [m for m in mine if m not in ADDED] == [
        m["name"] for m in lang["per_layer"]
        if not m["name"].startswith("tenant_")]
    assert mine[-3:] == ADDED
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in single["end_to_end"]]
    for m in cell["per_layer"]:
        if m["name"] in ADDED:
            assert m["workloads"] == [CELL]
            assert (m["layer"], m["moves"], m["source"]) == (
                "device_step", "ingest_tweets_per_s", "device_trace")


# ---------------------------------------------------------------------------
# the three readers, on a trace made by hand: an ``.xplane.pb`` written in
# the wire format ``stage_times.read_xspace`` reads (xplane.proto's field
# numbers are in that module's comments)

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode("utf-8")
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, ops: list, op_names: dict) -> bytes:
    """One ``XLA Ops`` line of ``(start_ps, end_ps, metadata id)`` events;
    ``op_names``: metadata id -> op-name path (none for a helper)."""
    line = _field(2, "XLA Ops") + _field(3, 0) + b"".join(
        _field(4, _field(1, meta) + _field(2, start) + _field(3, end - start))
        for start, end, meta in ops)
    metas = b"".join(
        _field(4, _field(1, key) + _field(2, (
            _field(1, key) + _field(2, f"fusion.{key}") + (
                _field(5, _field(1, 1) + _field(5, op_names[key]))
                if key in op_names else b""))))
        for key in sorted({m for _s, _e, m in ops}))
    stat_meta = _field(5, _field(1, 1) + _field(2, _field(1, 1)
                                                + _field(2, "tf_op")))
    return _field(1, _field(2, name) + _field(3, line) + metas + stat_meta)


P = "jit(shared)/cond/branch_1_fun/"
OP_NAMES = {
    1: "jit(shared)/repad/gather:",
    2: "jit(shared)/cond",
    3: P + "gram_count/dot_general:",
    4: P + "gram_matmul/dot_general:",
    5: P + "arm_map/while",
    6: P + "arm_map/while/body/closed_call/predict/reduce_sum:",
    7: P + "arm_map/while/body/closed_call/dual_loop/while:",
    8: P + "arm_map/while/body/closed_call/writeback/reduce_sum:",
    9: "jit(shared)/arm_map/while/body/closed_call/predict/reduce_sum:",
    # 10: a copy the compiler made, no op-name
}
US = 1_000_000   # picoseconds


def _batch(t0: int) -> list:
    """One batch of 2 arms, in microseconds from ``t0``: re-pad 100; the
    conditional 100..1900 holding the count build 300, G 500 and the map's
    while 1000 (an arm: a nameless copy 20, predict 130, dual loop 50,
    write-back 250, 50 of the while's own); the mapped stats 60 + 40
    of the second while; 2000 in all, 100 idle at the end."""
    ev = [(0, 100, 1), (100, 1900, 2), (100, 400, 3), (400, 900, 4),
          (900, 1900, 5)]
    for a in (0, 1):
        s = 900 + 500 * a
        ev += [(s, s + 20, 10), (s + 20, s + 150, 6), (s + 150, s + 200, 7),
               (s + 200, s + 450, 8)]
    ev += [(1900, 1960, 9)]
    return [((t0 + s) * US, (t0 + e) * US, m) for s, e, m in ev]


def test_readers_on_a_trace_made_by_hand(tmp_path, monkeypatch):
    apply_, shared, hbm = (
        manifest.load_module(manifest.layer_metric_path(n)) for n in ADDED)
    art = {"profile": {"busy_s": 0.00392, "window_s": 0.004, "batches": 2.0},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # no live run at all; then a live run of a program WITHOUT the scope
    # (the parent's, any other cell's): None, and nothing raised
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    monkeypatch.setattr(apply_, "_cache", {})
    assert [r.read(art) for r in (apply_, shared, hbm)] == [None] * 3
    assert [r.read({}) for r in (apply_, shared, hbm)] == [None] * 3
    work = tmp_path / CELL     # where the driver keeps a live run's files
    at = work / "profile" / "plugins" / "profile" / "2026_10_02"
    at.mkdir(parents=True)
    plain = {k: v.replace("arm_map/", "tenant_map/")
             for k, v in OP_NAMES.items()}
    trace = at / "t.xplane.pb"
    trace.write_bytes(_plane("/device:TPU:0", _batch(0) + _batch(2000), plain))
    test_contract.write_spans(work / "spans.json", [
        {"name": "tenant_rows", "ph": "i", "args": {
            "rows": [512] * 4, "bucket": 640, "pad_rows": 512}},
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}}])
    assert trace_files.xplane_file() == str(trace)
    assert [r.read(art) for r in (apply_, shared, hbm)] == [None] * 3

    # the cell's own: two batches, two arms, the bf16 plane
    monkeypatch.setattr(apply_, "_cache", {})
    trace.write_bytes(
        _plane("/device:TPU:0", _batch(0) + _batch(2000), OP_NAMES))
    test_contract.write_spans(work / "spans.json", [
        {"name": "tenant_rows", "ph": "i", "args": {
            "key": "all", "rows": [2048, 2048], "bucket": 2048,
            "pad_rows": 0}},
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}},
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}}])
    red = apply_.reduce(str(trace))
    assert red["busy_s"] == pytest.approx(2 * 1960e-6)
    # under the scope: the first while whole (1000) and the mapped stats
    assert red["arm_s"] == pytest.approx(2 * 1060e-6)
    # the nameless copy is under the scope (the while encloses it) and of
    # no stage: it counts in ``arm_s`` and not among the contractions
    assert red["arm_stage_s"]["predict"] == pytest.approx(
        2 * (2 * 130 + 60) * 1e-6)
    assert red["arm_stage_s"]["other"] == pytest.approx(
        2 * (2 * 20 + 2 * 50) * 1e-6)       # the copies, the while's own
    assert apply_.read(art) == pytest.approx(1.060 / 2)          # ms an arm
    assert shared.read(art) == pytest.approx(1.960 - 1.060)      # ms a batch
    # two reads of [2048, 2^18] bf16 = 2 GiB = 2.6219 ms at 819 GB/s, over
    # predict + write-back an arm: (2·130 + 60 + 2·250) µs ÷ 2 arms (a
    # made-up trace: its times are not a chip's, and its share no share)
    took_ms = (2 * 130 + 60 + 2 * 250) / 2 / 1e3
    assert hbm.read(art) == pytest.approx(
        100 * (2 * 2048 * 262144 * 2 / 819e9 * 1e3) / took_ms)
    assert hbm.needed_bytes(
        manifest.cell(manifest.load(), CELL)["config"], 2) == 2 ** 31
