"""The cell ``hash2e20-grid4-trimmed-280`` (PR 52: a champion and three
challengers on the SAME rows at 2^20 dims on the 2 x 2 mesh, ``--tenants 4
--tenantKey all --modelShards 2`` with a recipe an arm): the flags it hands
the program (recorded here until a ``benchmark`` PR moves them into
``test_contract.FLAGS``), what its files share with the two cells it reads
against (``hash2e20-trimmed-280``: M = 1, same mesh, same stream;
``hash2e18-grid4-trimmed-280``: same arms, one chip), the four-chip cells as
they now stand (two of eight), its two readers on a trace made by hand, and
— by hand, a few minutes each: a batch of 256 rows into 2^20 dims is
seconds on the CPU — the rehearsal on the four-device virtual mesh, the
control at the cell's own size, and the faults its comparison is there for,
shown as ``test_hash2e18_grid4.py`` shows its cell's:

1. every arm given arm 0's recipe (four copies of the champion);
2. arms 1 and 2 swapped (the right models in the wrong rows of the stack);
3. the HASH key in ``all``'s place (each recipe trained on a quarter of the
   rows; a partitioning key takes no model axis, so on the data-only mesh);
4. a step that returns its state unchanged (``test_hash2e20.BREAK_MESH``).

In all four every batch still counts its full rows, so ``count_diff`` stays
0 and ``weights_dev`` over the whole ``[4, F+4]`` array turns ``correct``
false. Their in-process twins at CPU sizes are
``tests/test_tenant_grid_mesh.py``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hash2e20_grid4.py -q
"""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import control, harness, manifest, stage_times, trace_files
from benchmark.drivers import train
from benchmark.tests import test_contract
from benchmark.tests.test_hash2e18_grid4 import (
    ARMS_1_AND_2_SWAPPED,
    EVERY_ARM_THE_CHAMPION,
    US,
    _plane,
)
from benchmark.tests.test_hash2e20 import BREAK_MESH

CELL = "hash2e20-grid4-trimmed-280"
MESH = "hash2e20-trimmed-280"        # M = 1, same mesh, same mix
GRID = "hash2e18-grid4-trimmed-280"  # the same arms on one chip
HASH2E20 = ["--numTextFeatures", "1048576", "--l2Reg", "0.1",
            "--batchBucket", "2048", "--modelShards", "2"]
ARMS = ["--tenants", "4", "--tenantKey", "all",
        "--tenantStepSize", "0.005,0.005,0.0025,0.0025",
        "--tenantL2Reg", "0.1,0.01,0.1,0.01"]
FLAGS = test_contract.SHARED + HASH2E20 + ARMS
ADDED = ["arm_writeback_hbm_share", "arm_collective_ms_per_batch"]
ARM_READERS = ["arm_apply_ms_per_arm", "arm_shared_ms_per_batch"]

# a partitioning key takes no model axis (apps/common.build_mesh refuses it),
# so the fault runs the hash-routed plane on the data-only mesh of the same
# four devices
THE_HASH_KEY_IN_ALLS_PLACE = """
from twtml_tpu.config import ConfArguments
_parse = ConfArguments.parse
def parse(self, argv):
    conf = _parse(self, argv)
    conf.tenantKey, conf.modelShards = "hash", 1
    return conf
ConfArguments.parse = parse
"""


def _drive(patch: str, seconds: int = 12) -> dict:
    code = patch + (
        "\nimport sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', '2147483659', "
        f"'--seconds', '{seconds}', '--trace', '0', '--rehearse']))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=manifest.ROOT,
                       env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# by hand: the rehearsal, the control, the four faults

def test_rehearsal_on_the_virtual_mesh():
    """``run.py --rehearse`` of the cell: feeder → trainer with four arms on
    the 2 x 2 virtual mesh → sink, the check run's ``[4, F+4]`` checkpoint
    and the window's first batches against the reference."""
    assert _drive("")["correct"] is True


@pytest.mark.parametrize("patch", [
    EVERY_ARM_THE_CHAMPION, ARMS_1_AND_2_SWAPPED, THE_HASH_KEY_IN_ALLS_PLACE,
    BREAK_MESH])
def test_fault_turns_correct_false_by_the_weights(patch):
    got = _drive(patch)
    assert got["correct"] is False
    n = got["numbers"]["weights_dev"]
    assert n["value"] > n["limit"], n
    assert got["numbers"]["count_diff"]["value"] == 0


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_control_bf16_is_not_correct_at_the_cells_own_size(seed):
    """Pure NumPy, so at the full 2^20 dims, batches of 2048, four arms; by
    ``weights_dev``, the limit that decides."""
    cell = manifest.cell(manifest.load(), CELL)
    got = control.run(cell, argparse.Namespace(seed=seed, control="bf16"))
    assert got["correct"] is False
    dev = got["numbers"]["weights_dev"]
    assert dev["value"] > 5 * dev["limit"]


# ---------------------------------------------------------------------------
# no rehearsal needed: tier-1 runs these too (tests/test_benchmark_contract.py)

def test_program_flags_are_the_recorded_list():
    cell = manifest.cell(manifest.load(), CELL)
    assert train.program_flags(
        cell["config"], "tpu", "CKPT", "http://sink") == FLAGS


def test_the_four_chip_cells_are_these_two_of_eight():
    """``test_hash2e20.py`` asserts (by hand-run) that ``hash2e20-trimmed-
    280`` is THE one four-chip cell; this PR may not edit it, so here is the
    assertion as it now reads (PERF.md §7 has the line for the next
    ``benchmark`` issue to restate the old one): two four-chip cells of
    eight, the manifest's quarter."""
    assert manifest.lint() == []
    workloads = manifest.load()["workloads"]
    four = [w["name"] for w in workloads if w["chips"] == 4]
    assert four == [MESH, CELL]
    assert len(workloads) == 8 and len(four) <= len(workloads) // 4


def test_the_cell_is_hash2e20_with_grid4s_four_recipes_on_its_rows():
    """``hash2e20``'s model, flags and layout with the plane's four keys
    added — the SAME four keys, values and flags ``hash2e18-grid4`` adds to
    ``hash2e18`` — on the mix all three share (the file that stands, by
    name); ``hash2e18``'s work count BY NAME (ONE Gram a batch over the
    cell's chips, whatever M); the grid's reference as it stands; the
    driver kind ``train``: no file of the harness had to change."""
    cell = manifest.cell(manifest.load(), CELL)
    mesh = manifest.cell(manifest.load(), MESH)
    grid = manifest.cell(manifest.load(), GRID)
    assert cell["traffic_path"] == mesh["traffic_path"] == grid["traffic_path"]
    assert cell["traffic"]["kind"] == "train"
    cfg, was, arms = cell["config"], mesh["config"], grid["config"]
    keys = ("tenants", "tenantKey", "tenantStepSize", "tenantL2Reg")
    model = dict(cfg["model"])
    assert [model.pop(k) for k in keys] == [arms["model"][k] for k in keys]
    assert model == was["model"]
    assert cfg["flags"] == was["flags"] + ARMS
    assert arms["flags"][-8:] == ARMS
    assert "--master" not in cfg["flags"]
    assert cfg["flags"][-3] == ",".join(
        str(s) for s in cfg["model"]["tenantStepSize"])
    assert cfg["flags"][-1] == ",".join(
        str(r) for r in cfg["model"]["tenantL2Reg"])
    assert (cfg["app"], cfg["batch_rows"], cfg["chips"]) == (
        was["app"], was["batch_rows"], was["chips"])
    assert cfg["must_span_devices"] == was["must_span_devices"] == 4
    assert "must_take_gram_plane" not in cfg and (
        "must_take_gram_plane" not in was)
    assert cfg["reference"] == arms["reference"] == (
        "benchmark/reference/grid_linear_sgd.py")
    assert manifest.work_count_path(cfg) == manifest.work_count_path(was)
    assert os.path.isfile(manifest.work_count_path(cfg))
    assert "statistic" not in cfg["correct"]        # half_up_integer
    limits = cfg["correct"]["limits"]
    for old in (was["correct"]["limits"], arms["correct"]["limits"]):
        assert set(limits) == set(old)
        assert (limits["count_diff"], limits["mse_dev"]) == (
            old["count_diff"], old["mse_dev"])
    assert cell["config_entry"]["reduced"] == []
    assert cell["workload"]["chips"] == 4


def test_the_cell_reports_hash2e20s_metrics_the_arms_two_and_its_own_two():
    """Every per-layer metric ``hash2e20-trimmed-280`` reports (the three
    collective ones among them); of ``hash2e18-grid4``'s three the two that
    read something (``arm_contraction_hbm_share`` has read nothing since PR
    50); none of the ``tenant_*`` five; and the two new ones, listed on THIS
    cell alone, on the layer ``device_step``, moving the rate."""
    cell = manifest.cell(manifest.load(), CELL)
    mesh = manifest.cell(manifest.load(), MESH)
    mine = [m["name"] for m in cell["per_layer"]]
    assert [m for m in mine if m not in ADDED + ARM_READERS] == [
        m["name"] for m in mesh["per_layer"]]
    assert not [m for m in mine if m.startswith("tenant_")]
    assert "arm_contraction_hbm_share" not in mine
    assert set(ARM_READERS) <= set(mine) and mine[-2:] == ADDED
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
    """``test_hash2e18_grid4.py``'s case of this name, AS IT NOW READS: it
    holds the three ``arm_*`` metrics to ``hash2e18-grid4-trimmed-280``
    ALONE, and ISSUE 52 lists two of them on this cell too (their readers
    read a four-plane profile as they stand). This PR may not edit that
    file, so tier-1 imports this one in its place
    (tests/test_benchmark_contract.py) and PERF.md §7 has the line for the
    next ``benchmark`` issue to restate the old one. Everything else it
    asserts is asserted here, word for word."""
    from benchmark.tests import test_hash2e18_grid4 as grid4

    cell = manifest.cell(manifest.load(), GRID)
    single = manifest.cell(manifest.load(), grid4.SINGLE)
    lang = manifest.cell(manifest.load(), grid4.LANG)
    mine = [m["name"] for m in cell["per_layer"]]
    assert [m for m in mine if m not in grid4.ADDED] == [
        m["name"] for m in single["per_layer"]]
    assert [m for m in mine if m not in grid4.ADDED] == [
        m["name"] for m in lang["per_layer"]
        if not m["name"].startswith("tenant_")]
    assert mine[-3:] == grid4.ADDED
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in single["end_to_end"]]
    for m in cell["per_layer"]:
        if m["name"] in grid4.ADDED:
            assert m["workloads"] == (
                [GRID, CELL] if m["name"] in ARM_READERS else [GRID])
            assert (m["layer"], m["moves"], m["source"]) == (
                "device_step", "ingest_tweets_per_s", "device_trace")


# ---------------------------------------------------------------------------
# the two readers, on a two-chip trace made by hand (the wire format of
# ``stage_times.read_xspace``; ``test_hash2e18_grid4._plane`` writes it)

S = "jit(sharded_train_step)/shard_map/"
B = S + "cond/branch_1_fun/"
OP_NAMES = {
    1: S + "repad/gather:",
    2: S + "cond",
    3: B + "gram_count/dot_general:",
    4: B + "predict/collective/psum:",          # u partials, [M, B/d]
    5: B + "predict/collective/all_gather:",    # u, [M, B]
    6: B + "gram_matmul/dot_general:",
    7: B + "gram_matmul/collective/psum:",      # the G panel: knows no arm
    8: B + "dual_loop/collective/psum:",        # ‖w_m‖², before the map
    9: B + "arm_map/while",
    10: B + "arm_map/while/body/closed_call/dual_loop/while:",
    11: B + "writeback/reduce_sum:",
    12: B + "writeback/collective/psum:",       # the [M, F/m] deltas
    13: S + "arm_map/quality/collective/psum:",  # stage ``other``: not theirs
}


def _batch(t0: int, delta_psum: int) -> list:
    """One batch on one chip, in microseconds from ``t0``: re-pad 100; the
    conditional 100..1900 holding the count build 300, the u psum 30 and
    all-gather 20, G 500 and its panel psum 50, the norms' psum 10, the
    map's while 400 (four arms' loops of 90, 40 of its own), the write-back
    pass 300 and its delta psum ``delta_psum``; the quality psum 20 after
    it; 1920 busy."""
    ev = [(0, 100, 1), (100, 1900, 2), (100, 400, 3), (400, 430, 4),
          (430, 450, 5), (450, 950, 6), (950, 1000, 7), (1000, 1010, 8),
          (1010, 1410, 9)]
    ev += [(1020 + 95 * a, 1110 + 95 * a, 10) for a in range(4)]
    ev += [(1410, 1710, 11), (1710, 1710 + delta_psum, 12), (1900, 1920, 13)]
    return [((t0 + s) * US, (t0 + e) * US, m) for s, e, m in ev]


def _write(work, op_names, spans):
    at = work / "profile" / "plugins" / "profile" / "2026_10_03"
    at.mkdir(parents=True, exist_ok=True)
    trace = at / "t.xplane.pb"
    trace.write_bytes(b"".join(   # chip 1 waits longer in the delta psum
        _plane(f"/device:TPU:{chip}",
               _batch(0, wait) + _batch(2000, wait), op_names)
        for chip, wait in ((0, 100), (1, 140))))
    test_contract.write_spans(work / "spans.json", spans)
    return trace


def test_readers_on_a_trace_made_by_hand(tmp_path, monkeypatch):
    hbm, coll = (manifest.load_module(manifest.layer_metric_path(n))
                 for n in ADDED)
    from benchmark.layer_metrics import arm_apply_ms_per_arm as arm_map

    art = {"profile": {"busy_s": 0.00384, "window_s": 0.004, "batches": 2.0},
           "peaks": {"hbm_bytes_per_s": 819e9}}

    def fresh():
        monkeypatch.setattr(arm_map, "_cache", {})
        monkeypatch.setattr(stage_times, "_cache", {})
        monkeypatch.setattr(coll, "_cache", {})

    # no live run at all, and no profile: None, nothing raised
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    fresh()
    assert [r.read(art) for r in (hbm, coll)] == [None, None]
    assert [r.read({}) for r in (hbm, coll)] == [None, None]

    # a live run of a program WITHOUT the scope (hash2e20's own step on the
    # same mesh: its collectives in those stages carry ONE model): None
    work = tmp_path / CELL     # where the driver keeps a live run's files
    plain = {k: v.replace("arm_map/", "") for k, v in OP_NAMES.items()}
    instants = [
        {"name": "tenant_rows", "ph": "i", "args": {
            "key": "all", "rows": [2048] * 4, "bucket": 2048, "pad_rows": 0,
            "mesh": [2, 2]}},
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}},
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}}]
    trace = _write(work, plain, instants)
    assert trace_files.xplane_file() == str(trace)
    assert [r.read(art) for r in (hbm, coll)] == [None, None]

    # the cell's own: two chips, two batches, four arms, the bf16 plane
    fresh()
    _write(work, OP_NAMES, instants)
    # the arm-carrying collectives: u psum 30 + u all-gather 20 + norms 10 +
    # the delta psum, 100 on chip 0 and 140 on chip 1 (it waits); NOT the
    # panel psum (gram_matmul) nor the quality psum (other). The chip with
    # the most of it, a batch:
    assert coll.per_chip_s(stage_times.read_xspace(str(trace))) == (
        pytest.approx([2 * 160e-6, 2 * 200e-6]))
    assert coll.read(art) == pytest.approx(0.200)
    # one read of the [1024, 2^19] bf16 row panel = 1 GiB = 1.311 ms at
    # 819 GB/s, over the write-back stage a batch, the mean over the chips:
    # (300 + 100 and 300 + 140) µs (a made-up trace: its times are not a
    # chip's, and its share no share)
    cfg = manifest.cell(manifest.load(), CELL)["config"]
    assert hbm.needed_bytes(cfg, 2) == 2 ** 30
    assert hbm.needed_bytes(manifest.cell(
        manifest.load(), GRID)["config"], 2) is None    # no --modelShards
    assert hbm.read(art) == pytest.approx(
        100 * (2 ** 30 / 819e9 * 1e3) / 0.420)
    # the arms' two standing readers read the four planes' mean as they
    # stand: under the scope the map's while (400) and the quality psum (20)
    apply_, shared = (manifest.load_module(manifest.layer_metric_path(n))
                      for n in ARM_READERS)
    assert apply_.read(art) == pytest.approx(0.420 / 4)
    assert shared.read(art) == pytest.approx((1.920 + 1.920) / 2 - 0.420)

    # a profile without the instants that say the plane: the share is left
    # out, the collectives' time needs none of them
    fresh()
    _write(work, OP_NAMES, instants[:1])
    assert hbm.read(art) is None
    assert coll.read(art) == pytest.approx(0.200)
