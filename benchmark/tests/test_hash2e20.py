"""What PR 27 added for the four-chip configuration ``hash2e20``: the cell
end to end on the four-device virtual mesh, its control, the collectives
reduction and the three readers. By hand (the two rehearsals take a few
minutes: a batch of 256 rows into 2^20 dims is seconds on the CPU):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hash2e20.py -q
"""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import collectives, control, manifest, stage_times

CELL = "hash2e20-trimmed-280"
HERE = os.path.join(manifest.HERE, "testdata")

# what test_correct.py's BREAK_TRAIN is to the single-device model: the
# mesh model's step returns its state unchanged
BREAK_MESH = """
import jax
from twtml_tpu.parallel import sharding
_step = sharding.ParallelSGDModel.step
def step(self, batch):
    w = jax.tree_util.tree_map(lambda a: a + 0, self._weights)  # donated below
    out = _step(self, batch)
    self._weights = w              # the state comes back unchanged
    return out
sharding.ParallelSGDModel.step = step
"""


def _reader(name):
    return manifest.load_module(manifest.layer_metric_path(name)).read


def _config():
    return manifest.load_json(
        os.path.join(manifest.HERE, "configs", "hash2e20.json"))


def test_manifest_lints_and_the_cell_is_the_one_four_chip_cell():
    assert manifest.lint() == []
    four = [w["name"] for w in manifest.load()["workloads"] if w["chips"] == 4]
    assert four == [CELL]
    cfg = _config()
    assert cfg["flags"][-2:] == ["--modelShards", "2"]
    assert "--master" not in cfg["flags"] and cfg["must_span_devices"] == 4
    # every training metric the one-chip cells report, and the three new ones
    cell = manifest.cell(manifest.load(), CELL)
    names = {m["name"] for m in cell["per_layer"]}
    old = {m["name"] for m in manifest.cell(
        manifest.load(), "hash2e18-trimmed-280")["per_layer"]}
    assert names - old == {
        "collective_ms_per_batch", "collective_ici_share", "chip_step_skew"}
    assert old <= names


@pytest.mark.parametrize("patch, want", [("", True), (BREAK_MESH, False)])
def test_rehearsal_on_the_virtual_mesh(patch, want):
    """``run.py --rehearse`` of the cell: feeder → trainer on the 2 x 2
    virtual mesh → sink, the check run and the window's first batches
    against the reference: ``correct`` true; with the mesh model's step
    broken underneath, false. (``benchmark.rehearse``'s 2 s window is too
    short for this cell on the CPU: 12 s here.)"""
    code = patch + (
        "\nimport sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', '2147483659', "
        "'--seconds', '12', '--trace', '0', '--rehearse']))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=manifest.ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"] is want


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_control_bf16_is_not_correct_at_the_cells_own_size(seed):
    """Pure NumPy, so at the full 2^20 dims and batches of 2048; by
    ``weights_dev``, the limit that decides (ten times under the control's
    smallest reading, PERF.md section 2)."""
    cell = manifest.cell(manifest.load(), CELL)
    got = control.run(cell, argparse.Namespace(seed=seed, control="bf16"))
    assert got["correct"] is False
    dev = got["numbers"]["weights_dev"]
    assert dev["value"] > 5 * dev["limit"]


# ---- the collectives reduction, on planes as ``read_xspace`` gives them ----

STEP = "jit(sharded_train_step)/shard_map"


def _plane(name, ops):
    """``ops``: ``[(start, end, instruction, op-name path)]``."""
    ids = {i + 1: op for i, op in enumerate(ops)}
    return {
        "name": name,
        "lines": [{"name": stage_times.OPS_LINE,
                   "events": [(s, e, i) for i, (s, e, _n, _p) in ids.items()]}],
        "event_name": {i: n for i, (_s, _e, n, _p) in ids.items()},
        "op_name": {i: p for i, (_s, _e, _n, p) in ids.items() if p},
    }


def _synthetic_planes():
    ms = 10**9   # the trace's clock is picoseconds
    chip0 = [
        (0, 4 * ms, "%fusion.1 = f32[] fusion()", f"{STEP}/gram_matmul/dot_general"),
        (4 * ms, 5 * ms, "%all-reduce.4 = f32[] all-reduce(%x)",
         f"{STEP}/gram_matmul/collective/psum"),
        # an asynchronous pair with compute between start and done
        (5 * ms, 5 * ms + 1000, "%all-gather-start.2 = (f32[]) all-gather-start(%y)",
         f"{STEP}/hash/collective/all_gather"),
        (5 * ms + 1000, 7 * ms, "%fusion.2 = f32[] fusion()", f"{STEP}/repad/gather"),
        (7 * ms, 7 * ms + 2000, "%all-gather-done.2 = f32[] all-gather-done(%s)",
         f"{STEP}/hash/collective/all_gather"),
        # an operand that refers to a collective is not under the scope
        (8 * ms, 9 * ms, "%fusion.3 = f32[] fusion(%all-reduce.4)", f"{STEP}/predict/add"),
    ]
    chip1 = [   # arrives early, waits 6 ms in one psum
        (0, 1 * ms, "%fusion.1 = f32[] fusion()", f"{STEP}/gram_matmul/dot_general"),
        (1 * ms, 7 * ms, "%all-reduce.4 = f32[] all-reduce(%x)",
         f"{STEP}/gram_matmul/collective/psum"),
    ]
    host = {"name": "/host:CPU", "lines": [], "event_name": {}, "op_name": {}}
    return [_plane("/device:TPU:0", chip0), _plane("/device:TPU:1", chip1), host]


def test_collective_time_is_the_union_of_in_flight_intervals_per_chip():
    red = collectives.reduce_planes(_synthetic_planes())
    assert red["chips"] == 2 and red["events"] == 4
    # chip 0: 1 ms synchronous + the pair from 5 ms to 7 ms + 2000 ps
    assert red["per_chip_s"][0] == pytest.approx(1e-3 + 2e-3 + 2e-9)
    assert red["per_chip_s"][1] == pytest.approx(6e-3)
    art = {"profile": {"batches": 2.0}}
    # the chip with the most of it, over the batches
    assert collectives.ms_per_batch(art, red) == pytest.approx(3.0)


def test_scope_is_a_part_of_the_path_not_a_substring():
    assert collectives.in_scope(f"{STEP}/predict/collective/psum_invariant:")
    assert not collectives.in_scope(f"{STEP}/predict/collective_like/add")
    assert not collectives.in_scope("")


def test_one_chip_trace_holds_nothing_under_the_scope():
    red = collectives.reduce(os.path.join(HERE, "scoped.xplane.pb"))
    assert red["chips"] == 1 and red["events"] == 0
    assert collectives.ms_per_batch({"profile": {"batches": 50.0}}, red) is None


def test_readers_leave_their_metric_out_where_there_is_nothing_to_read():
    """On a one-chip cell, on a program without a profile, and on the
    parent commit: None, never an exception."""
    for art in ({}, {"profile": None},
                {"profile": {"batches": 50.0, "busy_s": 1.9, "window_s": 2.0,
                             "per_chip": [{"busy_s": 1.9}]},
                 "work": {"flops": 1.0, "bytes": 1.0, "peak": "int8_ops"}}):
        assert _reader("collective_ms_per_batch")(art) is None
        assert _reader("collective_ici_share")(art) is None
        assert _reader("chip_step_skew")(art) is None


def test_chip_step_skew_is_busiest_over_least_busy():
    art = {"profile": {"per_chip": [
        {"busy_s": 1.90}, {"busy_s": 1.95}, {"busy_s": 1.92}, {"busy_s": 0}]}}
    assert _reader("chip_step_skew")(art) == pytest.approx(1.95 / 1.90)


def test_must_send_bytes_from_the_configurations_own_sizes():
    """2 x 2 at B = 2048, F = 2^20: the panel all-reduce 8 MiB, the G
    all-gather 8 MiB, the write-back all-reduce 2 MiB, half the wire — and
    200 GB/s carries that in under a tenth of a millisecond, so a measured
    in-flight time of milliseconds keeps the share far under 100%."""
    mod = manifest.load_module(manifest.layer_metric_path("collective_ici_share"))
    cfg = _config()
    panel = 1024 * 2048 * 4
    assert mod.must_send_bytes(cfg, 600e3) == (
        300e3 + panel + panel + (1 << 19) * 4)
    assert 1e3 * mod.must_send_bytes(cfg, 600e3) / mod.ICI_BYTES_PER_S < 0.1
    # 1 x 4: no data axis to gather over, a [2048, 2048] panel over four
    one_by_four = dict(cfg, flags=cfg["flags"][:-1] + ["4"])
    assert mod.must_send_bytes(one_by_four, 600e3) == 2 * 2048 * 2048 * 4 * 3 / 4
    # a configuration without the flag has no model axis: nothing to read
    assert mod.must_send_bytes(manifest.load_json(os.path.join(
        manifest.HERE, "configs", "hash2e18.json")), 600e3) is None
