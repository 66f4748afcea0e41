"""``--modelShards M`` (PR 27): the model axis at the entry point. The flag
parses and is validated at start-up with a plain message, ``build_mesh``
builds ``(devices / M) x M`` for the SGD learners and refuses M > 1 for
every other caller, the default builds the data-only mesh as before, and
``apps.linear_regression.run`` trains, traces, checkpoints and resumes on
the 2-D mesh — to one device and back — with nothing but the flag. Virtual
CPU devices (conftest.py pins 8; ``--master local[4]`` takes four)."""

import json
import os

import numpy as np
import pytest

import jax

from twtml_tpu.apps import common
from twtml_tpu.config import ConfArguments
from twtml_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLOSED = "http://127.0.0.1:9"
F_TEXT = 1 << 14
ROWS = 64


def _conf(*flags):
    return ConfArguments().parse(list(flags))


# ---- parse and validation --------------------------------------------------

def test_flag_parses_and_defaults_to_one():
    assert _conf().modelShards == 1
    assert _conf("--modelShards", "2").modelShards == 2
    assert "--modelShards" in ConfArguments().usage


@pytest.mark.parametrize("flags", [
    ["--modelShards", "0"], ["--modelShards", "-2"], ["--modelShards"],
])
def test_flag_refuses_what_is_not_a_positive_count(flags, capsys):
    with pytest.raises(SystemExit) as exc:
        _conf(*flags)
    assert exc.value.code == 1
    assert "--modelShards" in capsys.readouterr().out   # the usage text


@pytest.mark.parametrize("flags, what", [
    # 3 does not divide four devices
    (["--modelShards", "3", "--master", "local[4]",
      "--numTextFeatures", "16384"], "must divide"),
    # 4 divides the devices, not 1,002 features
    (["--modelShards", "4", "--master", "local[4]",
      "--numTextFeatures", "1002"], "must divide"),
    # one device has no model axis to give
    (["--modelShards", "2", "--master", "local[1]",
      "--numTextFeatures", "16384"], "must divide"),
])
def test_shards_must_divide_devices_and_features(flags, what):
    with pytest.raises(SystemExit) as exc:
        common.build_mesh(_conf(*flags), model_axis=True)
    assert what in str(exc.value) and "--modelShards" in str(exc.value)


def test_callers_without_a_model_axis_refuse_the_flag():
    """k-means and the tenant plane build a data-only mesh: a flag they
    would ignore is an error, never another deployment in silence."""
    conf = _conf("--modelShards", "2", "--master", "local[4]",
                 "--numTextFeatures", "16384")
    with pytest.raises(SystemExit) as exc:
        common.build_mesh(conf, what="clustering")
    assert "clustering" in str(exc.value)
    with pytest.raises(SystemExit):
        common.build_model(_conf(
            "--modelShards", "2", "--master", "local[4]", "--tenants", "2",
            "--numTextFeatures", "16384"))


@pytest.mark.parametrize("shards, shape", [
    (2, {"data": 2, "model": 2}), (4, {"data": 1, "model": 4}),
])
def test_mesh_is_devices_over_m_by_m(shards, shape):
    mesh = common.build_mesh(
        _conf("--modelShards", str(shards), "--master", "local[4]",
              "--numTextFeatures", "16384"), model_axis=True)
    assert mesh.axis_names == ("data", "model") and dict(mesh.shape) == shape
    assert list(mesh.devices.flat) == jax.devices()[:4]


@pytest.mark.parametrize("model_axis", [False, True])
def test_default_builds_the_data_only_mesh_as_before(model_axis):
    """With the flag absent — both ``hash2e18`` cells, every run there was
    before it — the mesh is the parent's: ``('data',)`` over the devices,
    None on one device."""
    conf = _conf("--master", "local[4]", "--numTextFeatures", "1048576",
                 "--batchBucket", "2048")
    mesh = common.build_mesh(conf, model_axis=model_axis)
    assert mesh.axis_names == ("data",)
    assert mesh == make_mesh(num_data=4, devices=jax.devices()[:4])
    one = _conf("--master", "local[1]", "--numTextFeatures", "262144")
    assert common.build_mesh(one, model_axis=model_axis) is None
    model, multiple = common.build_model(one)
    assert multiple == 1 and not hasattr(model, "mesh")


# ---- the entry point -------------------------------------------------------

def _run(tmp_path, name, *flags, max_batches=2, ckpt="ckpt"):
    from twtml_tpu.apps import linear_regression

    span_file = str(tmp_path / f"{name}.spans.json")
    conf = _conf(
        "--source", "replay", "--replayFile",
        os.path.join(ROOT, "tests", "data", "tweets.jsonl"),
        "--seconds", "0", "--backend", "cpu", "--l2Reg", "0.1",
        "--batchBucket", str(ROWS), "--numTextFeatures", str(F_TEXT),
        "--checkpointDir", str(tmp_path / ckpt), "--twtweb", CLOSED,
        "--lightning", CLOSED, "--trace", span_file, *flags,
    )
    totals = linear_regression.run(conf, max_batches=max_batches)
    with open(span_file, encoding="utf-8") as fh:
        events = [json.loads(line.rstrip(",\n")) for line in fh
                  if line.startswith("{")]
    return totals, events


def _named(events, name):
    return [ev for ev in events if ev.get("name") == name]


def _crc_lines(run):
    """The ``state crc`` log lines of apps/common while ``run()`` runs."""
    import logging

    seen = []
    handler = logging.Handler()
    handler.emit = lambda rec: seen.append(rec.getMessage())
    logger = logging.getLogger("twtml_tpu.apps.common")
    logger.addHandler(handler)
    try:
        return run(), seen
    finally:
        logger.removeHandler(handler)


def test_entry_point_trains_traces_and_hands_state_over(
    tmp_path, clean_properties
):
    """``run`` with ``--modelShards 2`` on four devices: the 2 x 2 mesh,
    said once in the trace; the Gram plane delivered with every batch's
    stats; ``device_span`` in the totals; a FLAT verified checkpoint that
    the serving plane loads on one device, that a one-device run resumes
    from, and whose successor a sharded run resumes from in turn."""
    from twtml_tpu.serving import load_servable

    four = ("--master", "local[4]", "--modelShards", "2")
    totals, events = _run(tmp_path, "first", *four)
    assert totals["device_span"] == {"weights": 4, "batch": 4}
    (layout,) = _named(events, "mesh_layout")
    assert layout["args"] == {
        "data": 2, "model": 2, "f_text_local": F_TEXT // 2, "devices": 4}
    planes = _named(events, "gram_plane")
    assert len(planes) == totals["batches"] >= 1
    assert all(ev["args"]["plane"] >= 1 for ev in planes)   # never -1
    compiled = {ev["args"]["fun"] for ev in _named(events, "compile")}
    assert "jit(sharded_train_step)" in compiled
    assert "jit(train_step)" not in compiled

    snapshot, reason = load_servable(str(tmp_path / "ckpt"))
    assert snapshot is not None, reason
    assert snapshot.weights.shape == (F_TEXT + 4,)
    assert np.any(snapshot.weights[:F_TEXT] != 0)
    crc = common.state_checksum(snapshot.weights)

    # one device resumes from the sharded run's archive: the crc it logs
    # at restore is the archive's
    (again, events1), seen = _crc_lines(lambda: _run(
        tmp_path, "one", "--master", "local[1]",
        max_batches=totals["batches"]))
    assert any(f"state crc {crc}" in m for m in seen), seen
    assert again["count"] >= totals["count"]
    assert not _named(events1, "mesh_layout") and "device_span" not in again

    # and back: the sharded run resumes from what one device left
    after, _reason = load_servable(str(tmp_path / "ckpt"))
    crc1 = common.state_checksum(after.weights)
    (_back, events2), seen = _crc_lines(lambda: _run(
        tmp_path, "back", *four, max_batches=again["batches"]))
    assert any(f"state crc {crc1}" in m for m in seen), seen
    assert _named(events2, "mesh_layout")[0]["args"]["model"] == 2


def test_entry_point_without_the_flag_runs_data_parallel(
    tmp_path, clean_properties
):
    totals, events = _run(tmp_path, "dp", "--master", "local[4]",
                          max_batches=1)
    (layout,) = _named(events, "mesh_layout")
    assert layout["args"] == {
        "data": 4, "model": 1, "f_text_local": F_TEXT, "devices": 4}
    assert totals["device_span"] == {"weights": 4, "batch": 4}
