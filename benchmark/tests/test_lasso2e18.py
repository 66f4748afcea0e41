"""The cell ``lasso2e18-trimmed-280`` (PR 55: ``hash2e18-trimmed-280``'s
stream under MLlib's ``L1Updater``, ``--l1Reg 0.1`` — the learner whose 50
iterations cannot run in the Gram basis and read the count matrix instead):
the flags it hands the program (recorded here until a ``benchmark`` PR moves
them into ``test_contract.FLAGS``), what its files share with its control
cell, its four readers on a trace and a span file made by hand, two contract
cases of files this PR may not edit AS THEY NOW READ, and the faults its
comparison is there for:

1. a step that returns its state unchanged;
2. the L2 updater in L1's place at the same strength (``hash2e18`` itself);
3. 49 iterations;
4. the reference's bf16 control in the program's place.

In each every batch still counts its rows, so ``count_diff`` stays 0 and
``weights_dev`` turns ``correct`` false. A FIFTH fault the issue names — the
threshold left off the four numeric weights — the cell's limits CANNOT see,
and the case below says so with the arithmetic instead of pretending: at
this deployment's hand scaling (followers x 1e-12) a numeric weight's
gradient step is ~2.5e-6 a round against a threshold of 5e-4, so MLlib's
``L1Updater`` pins all four at exactly 0.0, and a program that skipped them
would move ``weights_dev`` by ~1e-8 of a limit of ~1e-5. Holding the four
to the reference's exact 0.0 takes a number of its own, and
``benchmark/compare.py`` — a file this PR may not edit — holds exactly
three (``count_diff``, the statistic's, ``weights_dev``) and reads no other
key of ``correct.limits``: the next ``benchmark`` issue's to add (PERF.md
section 7 row 27 (e)). What does hold the program to it is tier-1: ``tests/test_l1_updater.py`` asserts the four are
exactly zero, as the reference's are, and that the rule thresholds every
leaf. Each fault run is ``run.py``'s own path at rehearsal sizes with the
fault patched in underneath (minutes each at 2^18 dims on a CPU: run by
hand; their in-process twins are in ``tests/test_l1_updater.py``); unbroken
it is ``test_correct.py``'s case of this cell.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lasso2e18.py -q
"""

import argparse
import os

import pytest

from benchmark import control, harness, manifest, trace_files
from benchmark.drivers import train
from benchmark.tests import test_contract
from benchmark.tests.test_correct import BREAK_TRAIN, _cell, _drive
from benchmark.tests.test_hash2e18_grid4 import US, _plane
from benchmark.tests.test_hash2e20_grid4 import CELL as MESH_GRID, MESH

CELL = "lasso2e18-trimmed-280"
CONTROL = "hash2e18-trimmed-280"     # same stream, sizes and strength: L2
FLAGS = test_contract.SHARED + [
    "--numTextFeatures", "262144", "--l1Reg", "0.1", "--batchBucket", "2048",
    "--master", "local[1]"]
ADDED = ["primal_loop_ms_per_batch", "primal_pass_hbm_share",
         "primal_iterations_per_batch", "weights_zero_share"]
# three stages this program does not have, and the roofline share whose work
# count cannot see the rounds a batch needed (work_counts/lasso2e18.py)
NOT_IN_THIS_PROGRAM = ["step_roofline", "stage_ms.gram_matmul",
                       "stage_ms.dual_loop", "stage_ms.writeback"]

_FROM_CONF = """
from twtml_tpu.models.sgd import StreamingSGDModel
_from_conf = StreamingSGDModel.from_conf.__func__
StreamingSGDModel.from_conf = classmethod(
    lambda cls, conf, **kw: _from_conf(cls, conf, **dict(kw, %s)))
"""
L2_IN_L1S_PLACE = _FROM_CONF % "l1_reg=0.0, l2_reg=0.1"
FORTY_NINE_ITERATIONS = _FROM_CONF % "num_iterations=49"


@pytest.mark.parametrize("patch", [
    BREAK_TRAIN, L2_IN_L1S_PLACE, FORTY_NINE_ITERATIONS])
def test_fault_turns_correct_false_by_the_weights(patch):
    got = _drive(CELL, patch)
    assert got["correct"] is False
    n = got["numbers"]["weights_dev"]
    assert n["value"] > n["limit"], n
    assert got["numbers"]["count_diff"]["value"] == 0


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_the_bf16_control_is_not_correct(seed):
    """``test_correct.py`` has this case for every cell of the manifest; it
    is restated for this one so that the file lists all its faults."""
    args = argparse.Namespace(seed=seed, control="bf16")
    got = control.run(_cell(CELL), args)
    assert got["correct"] is False
    n = got["numbers"]["weights_dev"]
    assert n["value"] > n["limit"], n


def test_the_limits_cannot_see_the_four_numeric_weights():
    """The arithmetic of the docstring, from the files: the largest numeric
    feature times the largest residual, a step, against the threshold."""
    cell = manifest.cell(manifest.load(), CELL)
    m, g = cell["config"]["model"], cell["traffic"]["generator"]
    largest_feature = 2_000_000 * 1e-12          # gen.py's followers, scaled
    step = m["stepSize"] * g["retweets_max"] * largest_feature
    threshold = m["stepSize"] / m["numIterations"] ** 0.5 * m["l1Reg"]
    assert step < threshold / 7     # every round, the last (smallest) too
    assert cell["config"]["correct"]["limits"]["weights_dev"] > 1e-6


def test_program_flags_are_the_recorded_list():
    cell = manifest.cell(manifest.load(), CELL)
    assert train.program_flags(
        cell["config"], "tpu", "CKPT", "http://sink") == FLAGS


def test_the_cell_is_hash2e18_trimmed_280_under_the_l1_updater():
    """``hash2e18``'s model with the strength moved from ``l2Reg`` to
    ``l1Reg`` and its flags with the one flag renamed; the SAME generator
    as the control cell's mix by name (so the same pool, byte for byte),
    its feeder, check batches and warm-up, and a longer profiled stretch —
    the one reason the mix is a file of its own; a reference and a work
    count of its own; the driver kind ``train``: no file of the harness had
    to change."""
    cell = manifest.cell(manifest.load(), CELL)
    base = manifest.cell(manifest.load(), CONTROL)
    cfg, was = cell["config"], base["config"]
    assert cfg["model"] == dict(was["model"], l2Reg=0.0, l1Reg=0.1)
    assert cfg["flags"] == [
        "--l1Reg" if f == "--l2Reg" else f for f in was["flags"]]
    assert cfg["app"] == was["app"] and cfg["batch_rows"] == was["batch_rows"]
    assert cfg["must_take_gram_plane"] is True
    assert cfg["reference"] == "benchmark/reference/lasso_sgd.py"
    assert manifest.work_count_path(cfg).endswith("work_counts/lasso2e18.py")
    assert "statistic" not in cfg["correct"]        # half_up_integer
    limits, old = cfg["correct"]["limits"], was["correct"]["limits"]
    assert set(limits) == set(old)
    assert (limits["count_diff"], limits["mse_dev"]) == (
        old["count_diff"], old["mse_dev"])
    assert cell["config_entry"]["reduced"] == []
    assert cell["workload"]["chips"] == 1
    mix, std = cell["traffic"], base["traffic"]
    raw = manifest.load_json(cell["traffic_path"])
    assert raw["generator_of"] == "trimmed-kept-280" and "generator" not in raw
    assert mix["generator"] == std["generator"]
    assert mix["kind"] == std["kind"] == "train"
    for key in ("feeder", "check_batches", "warmup", "kept_out"):
        assert mix[key] == std[key], key
    assert (mix["profile_seconds"], std["profile_seconds"]) == (6.0, 2.0)
    assert cell["traffic_path"].endswith(".json")     # data, not code


def test_the_work_count_is_the_most_a_batch_reads_and_feeds_no_roofline():
    """``work`` is given the configuration alone: it counts all 50 rounds,
    the MOST a batch can read, where MLlib's loop breaks at convergence (~9
    on this mix). So ``step_roofline`` does not list the cell (an early
    exit would read over 100% against this count);
    ``primal_pass_hbm_share`` is its roofline share, from the rounds the
    program counted, and both price a round as ONE read of ``[B, F]``."""
    loaded = manifest.load()
    cell = manifest.cell(loaded, CELL)
    work = manifest.load_module(
        manifest.work_count_path(cell["config"])).work(cell["config"], 1, 1e6)
    b, f, rounds = 2048, 262144, 50
    assert work["bytes"] == (1 + rounds) * b * f + 1e6
    assert work["flops"] == 4 * b * f * rounds
    peaks = harness.peaks_for("TPU v5e")
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    assert 0.0334 < t_mem < 0.0335                       # ~33.4 ms, binding
    assert work["flops"] / peaks[work["peak"]] < t_mem / 50
    roofline = next(m for m in loaded["per_layer"]
                    if m["name"] == "step_roofline")
    assert CELL not in roofline["workloads"]
    assert CONTROL in roofline["workloads"]
    hbm = manifest.load_module(
        manifest.layer_metric_path("primal_pass_hbm_share"))
    assert hbm.needed_bytes(cell["config"], rounds, 1) == rounds * b * f


def test_the_cell_reports_its_controls_metrics_less_four_and_its_own_four():
    """Everything ``hash2e18-trimmed-280`` reports but the three stages this
    program does not have (a reader with nothing to read would print a null
    on the ledger) and ``step_roofline`` (above), in the control's order, then the four ``primal_*`` /
    ``weights_zero_share``, listed on THIS cell alone, on the layer
    ``device_step``, moving the rate — standing after ``publish_ms_p95``,
    which was appended for every cell before them."""
    loaded = manifest.load()
    cell = manifest.cell(loaded, CELL)
    base = manifest.cell(loaded, CONTROL)
    mine = [m["name"] for m in cell["per_layer"]]
    assert [m for m in mine if m not in ADDED] == [
        m["name"] for m in base["per_layer"]
        if m["name"] not in NOT_IN_THIS_PROGRAM]
    assert mine[-4:] == ADDED
    assert [m["name"] for m in loaded["per_layer"]][-4:] == ADDED
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in base["end_to_end"]]
    by_name = {m["name"]: m for m in cell["per_layer"]}
    for name in ADDED:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["moves"]) == (
            "device_step", "ingest_tweets_per_s")
        assert m["source"] == (
            "device_trace" if name.startswith("primal_loop")
            or name.startswith("primal_pass") else "program_counter")
    for name in NOT_IN_THIS_PROGRAM:
        entry = next(m for m in loaded["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]
    for name in ("gram_fast_plane_share", "stage_ms.other"):
        assert name in mine


# -- two cases of files this PR may not edit, as they now read ---------------

def test_the_four_chip_cells_are_these_two_of_nine():
    """``test_hash2e20_grid4.py::test_the_four_chip_cells_are_these_two_of_
    eight`` asserts ``len(workloads) == 8``; a ninth cell on ONE chip leaves
    the four-chip cells the same two, inside the manifest's quarter
    (``9 // 4 = 2``). PERF.md section 7 row 26 has the line for the next
    ``benchmark`` issue to restate the old one in its own file."""
    assert manifest.lint() == []
    workloads = manifest.load()["workloads"]
    four = [w["name"] for w in workloads if w["chips"] == 4]
    assert four == [MESH, MESH_GRID]
    assert len(workloads) == 9 and len(four) <= len(workloads) // 4
    assert workloads[-1]["name"] == CELL and workloads[-1]["chips"] == 1


def test_publish_ms_p95_lists_every_cell_and_a_cells_own_metrics_follow_it():
    """``test_publish_ms_p95.py::test_the_metric_is_the_last_entry_and_every_
    cell_lists_it`` holds the metric to ``per_layer[-1]`` and to the END of
    every cell's list. As it now reads: the entry is what PR 53 wrote, its
    ``workloads`` every cell of the manifest (the new one appended), and in
    every cell's list nothing stands after it but metrics that cell ALONE
    reports, appended by the PR that added the cell."""
    loaded = manifest.load()
    entry = next(m for m in loaded["per_layer"]
                 if m["name"] == "publish_ms_p95")
    assert entry == {
        "name": "publish_ms_p95", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "publish",
        "moves": "batch_gap_ms_p95",
        "workloads": [w["name"] for w in loaded["workloads"]]}
    for w in loaded["workloads"]:
        names = [m for m in manifest.cell(loaded, w["name"])["per_layer"]]
        after = names[[m["name"] for m in names].index("publish_ms_p95") + 1:]
        assert all(m["workloads"] == [w["name"]] for m in after), w["name"]
        assert [m["name"] for m in after] == (
            ADDED if w["name"] == CELL else [])
    assert manifest.lint() == []


# -- the four readers, on a trace and a span file made by hand ---------------

P = "jit(train_step)/cond/branch_1_fun/"
BODY = P + "primal_loop/while/body/closed_call/"
OP_NAMES = {
    1: "jit(train_step)/repad/gather:",
    2: "jit(train_step)/cond",
    3: P + "gram_count/dot_general:",
    4: P + "predict/reduce_sum:",
    5: P + "primal_loop/while",
    6: BODY + "primal_pass/cond/branch_0_fun/primal_pass:",   # the kernel
    7: BODY + "jit(_where)/select_n:",                        # the updater
    8: P + "primal_loop/concatenate:",
    9: "jit(train_step)/eq:",                                 # the zero count
    # 10: a copy the compiler made, no op-name
}


def _batch(t0: int, rounds: int = 3) -> list:
    """One batch, in microseconds from ``t0``: re-pad 100; the conditional
    100..(700 + 500·rounds) holding the count build 300 with predict's
    epilogue 100 and the loop's while — a round: a nameless copy 20, the
    pass 400, the updater 60, 20 of the while's own — then the concatenate
    50 inside the scope; the zero count 30 outside; 70 idle at the end."""
    loop_end = 500 + 500 * rounds
    ev = [(0, 100, 1), (100, loop_end + 50, 2), (100, 400, 3), (400, 500, 4),
          (500, loop_end, 5)]
    for k in range(rounds):
        s = 500 + 500 * k
        ev += [(s, s + 20, 10), (s + 20, s + 420, 6), (s + 420, s + 480, 7)]
    ev += [(loop_end, loop_end + 50, 8), (loop_end + 50, loop_end + 80, 9)]
    return [((t0 + s) * US, (t0 + e) * US, m) for s, e, m in ev]


def test_readers_on_a_trace_and_a_span_file_made_by_hand(tmp_path,
                                                         monkeypatch):
    loop, hbm, rounds, zeros = (
        manifest.load_module(manifest.layer_metric_path(n)) for n in ADDED)
    readers = (loop, hbm, rounds, zeros)
    batch_us = 2080 + 70
    art = {"profile": {"busy_s": 2 * 2080e-6, "window_s": 2 * batch_us * 1e-6,
                       "batches": 2.0},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # no live run at all; then a live run of a program WITHOUT the scope and
    # the instant (the parent's, any other cell's): None, nothing raised
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    monkeypatch.setattr(loop, "_cache", {})
    assert [r.read(art) for r in readers] == [None] * 4
    assert [r.read({}) for r in readers] == [None] * 4
    work = tmp_path / CELL     # where the driver keeps a live run's files
    at = work / "profile" / "plugins" / "profile" / "2026_10_04"
    at.mkdir(parents=True)
    plain = {k: v.replace("primal_loop/", "dual_loop/")
             for k, v in OP_NAMES.items()}
    trace = at / "t.xplane.pb"
    trace.write_bytes(
        _plane("/device:TPU:0", _batch(0) + _batch(batch_us), plain))
    test_contract.write_spans(work / "spans.json", [
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}}])
    assert trace_files.xplane_file() == str(trace)
    assert [r.read(art) for r in readers] == [None] * 4

    # the cell's own: two batches of three rounds on the bf16 plane, of
    # which the second froze after two
    monkeypatch.setattr(loop, "_cache", {})
    trace.write_bytes(
        _plane("/device:TPU:0", _batch(0) + _batch(batch_us), OP_NAMES))
    test_contract.write_spans(work / "spans.json", [
        {"name": "gram_plane", "ph": "i", "args": {"plane": 1}},
        {"name": "primal", "ph": "i", "args": {
            "batch": 1, "iterations": 3, "zero_weights": 262144 - 4096,
            "plane": 1}},
        {"name": "primal", "ph": "i", "args": {
            "batch": 2, "iterations": 2, "zero_weights": 262144 - 12288,
            "plane": 1}}])
    red = loop.reduce(str(trace))
    assert red["busy_s"] == pytest.approx(2 * 2080e-6)
    # under the scope: the while whole (1500: the nameless copies are in
    # it) and the concatenate; not the count build, predict, the zero count
    assert red["loop_s"] == pytest.approx(2 * 1550e-6)
    assert loop.read(art) == pytest.approx(1.550)              # ms a batch
    assert rounds.read(art) == pytest.approx(2.5)
    assert zeros.read(art) == pytest.approx(100 * (1 - 8192 / 262144))
    # 2.5 rounds x one read of [2048, 2^18] bf16 = 2.5 GiB = 3.2774 ms at
    # 819 GB/s over the loop's 1.55 ms (a made-up trace: its times are not a
    # chip's, and its share no share)
    assert hbm.read(art) == pytest.approx(
        100 * (2.5 * 2048 * 262144 * 2 / 819e9 * 1e3) / 1.550)
    assert hbm.needed_bytes(
        manifest.cell(manifest.load(), CELL)["config"], 50, 2) == 50 * 2 ** 30
    assert os.path.isfile(manifest.layer_metric_path("stage_ms.other"))
