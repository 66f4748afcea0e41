"""The feature-sharded (2-D mesh, ``--modelShards``) train step as part of
the traced, observed program (PR 27): on the ragged wire against the
single-device step and the benchmark's plain float64 reference at every
layout of four devices; its model shards' partial Gram panels against the
unsharded ``text_gram`` with the plane gate reduced over the model axis;
its checkpoint through the flat ``[F+4]`` vector to one device and back;
the stage names, the ``collective`` scope and the module name of the
compiled step. Virtual CPU devices, small sizes (the chip's are in
PERF.md)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmark.reference import linear_sgd
from twtml_tpu.apps.common import AppCheckpoint, state_checksum
from twtml_tpu.config import ConfArguments
from twtml_tpu.features.batch import (
    FeatureBatch,
    RaggedUnitBatch,
    UnitBatch,
    pack_batch,
)
from twtml_tpu.features.featurizer import Featurizer
from twtml_tpu.models import StreamingLinearRegressionWithSGD
from twtml_tpu.models.sgd import STAGE_SCOPES
from twtml_tpu.ops import gram as gram_ops
from twtml_tpu.ops.quality import QUALITY_INDEX
from twtml_tpu.parallel import ParallelSGDModel, make_mesh
from twtml_tpu.streaming.sources import SyntheticSource


def text_gram(*args, **kwargs):
    """``(G, plane)``: the switch with G alone as its body."""
    return gram_ops.text_gram(*args, body=gram_ops.CountPlane.gram, **kwargs)


F_TEXT = 1 << 14
ROWS = 64
NOW_MS = 1785320000000
KW = dict(num_text_features=F_TEXT, num_iterations=50, step_size=0.005,
          l2_reg=0.1)
# benchmark/configs/hash2e18.json's limit on Σ|w−w_ref| ÷ Σ|w_ref|, the
# scale float32 leaves after a few batches
WEIGHTS_DEV_LIMIT = 8.8e-6
LAYOUTS = [(2, 2), (1, 4), (4, 1)]


def _mesh(num_data=2, num_model=2):
    return make_mesh(num_data=num_data, num_model=num_model,
                     devices=jax.devices()[:num_data * num_model])


def _seeded_weights(seed=7):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=F_TEXT + 4) * 0.3).astype(np.float32)


def _statuses(n_batches=2):
    """Seeded retweets, every fifth with upper-case non-ASCII in its text
    (the wire then carries 16-bit units and the fold has work)."""
    out = list(SyntheticSource(
        total=ROWS * n_batches, seed=11, base_ms=NOW_MS).produce())
    for s in out[::5]:
        s.retweeted_status.text += " ÜBER Ñandú"
    return [out[i:i + ROWS] for i in range(0, len(out), ROWS)]


# ---- (i) every layout, ragged wire: single-device step and reference ------

@pytest.fixture(scope="module")
def reference_run():
    """The plain float64 reference and the single-device step over two
    seeded batches from seeded weights: ``(ref, single, per-batch stats)``."""
    w0 = _seeded_weights()
    ref = linear_sgd.LinearSGD(
        F_TEXT, num_iterations=50, step_size=0.005, l2_reg=0.1)
    ref.w = w0.astype(np.float64)
    single = StreamingLinearRegressionWithSGD(quality=True, **KW)
    single.set_initial_weights(w0)
    feat = Featurizer(now_ms=NOW_MS, num_text_features=F_TEXT)
    stats = []
    for chunk in _statuses():
        orig = [s.retweeted_status for s in chunk]
        rows, cols, numeric = linear_sgd.featurize(
            [o.text for o in orig], [o.followers_count for o in orig],
            [o.favourites_count for o in orig],
            [o.friends_count for o in orig],
            [o.created_at_ms for o in orig], NOW_MS, F_TEXT)
        labels = np.array([o.retweet_count for o in orig], np.float64)
        ref_stats = ref.step_batch(rows, cols, numeric, labels)
        out1 = single.step(pack_batch(feat.featurize_batch_ragged(
            chunk, row_bucket=ROWS, unit_bucket=64)))
        stats.append((ref_stats, out1))
    return ref, single, stats


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: f"{l[0]}x{l[1]}")
def test_layout_on_the_ragged_wire_against_single_device_and_reference(
    reference_run, layout
):
    ref, single, stats = reference_run
    model = ParallelSGDModel(_mesh(*layout), quality=True, **KW)
    model.set_initial_weights(_seeded_weights())
    feat = Featurizer(now_ms=NOW_MS, num_text_features=F_TEXT)
    for chunk, (ref_stats, out1) in zip(_statuses(), stats):
        rb = feat.featurize_batch_ragged(
            chunk, row_bucket=ROWS, unit_bucket=64)
        assert rb.units.dtype == np.uint16
        out = model.step(model.pack_for_wire(rb))
        # counts exact, against both
        assert float(out.count) == ref_stats["count"] == float(out1.count)
        assert abs(float(out.mse) - ref_stats["mse"]) <= (
            2e-5 * ref_stats["mse"] + 1)
        for field in ("mse", "real_stdev", "pred_stdev"):
            np.testing.assert_allclose(
                float(getattr(out, field)), float(getattr(out1, field)),
                rtol=1e-5)
        q, q1 = np.asarray(out.quality), np.asarray(out1.quality)
        np.testing.assert_allclose(q, q1, rtol=2e-4, atol=1e-3)
        # the plane leaves the step: short rows, so the s8 plane — not −1
        assert q[QUALITY_INDEX["gram_plane"]] == 2.0
    w = model.latest_weights
    np.testing.assert_allclose(
        w, single.latest_weights, rtol=2e-5, atol=2e-6)
    dev = np.sum(np.abs(w.astype(np.float64) - ref.w)) / np.sum(np.abs(ref.w))
    assert dev <= WEIGHTS_DEV_LIMIT, dev
    assert model.device_span() == {"weights": 4, "batch": 4}


# ---- (ii) the shares add up, and every shard takes the same plane ---------

def _plane_batch(plane: str):
    """(idx, val) whose UNSHARDED gate takes the named plane."""
    rng = np.random.default_rng(5)
    b, slots = 16, 300
    idx = rng.integers(0, F_TEXT, (b, slots)).astype(np.int32)
    val = np.zeros((b, slots), np.float32)
    if plane == "s8":
        val[:, :100] = 1.0                       # row mass 100
    elif plane == "bf16_rung1":
        val[:, :200] = 1.0                       # row mass 200
    elif plane == "bf16_rung2":
        val[:, :290] = 1.0                       # row mass 290 > 255
    elif plane == "exact":
        val[:, :290] = 1.0
        idx[3, :257] = 4242                      # one feature 257 times
    elif plane == "fractional":
        val[:, :100] = 1.0
        val[5, 7] = 0.5                          # one value, one slice
    return jnp.asarray(idx * (val > 0)), jnp.asarray(val)


def _shard_panels(idx, val, num_model):
    """``text_gram`` on each model shard's slice as the 2-D step calls it
    (indices relative to the slice, values zeroed outside it, the gate
    reduced over ``model``): the stacked partial Gs and every shard's
    plane index."""
    mesh = make_mesh(num_data=1, num_model=num_model,
                     devices=jax.devices()[:num_model])
    f_local = F_TEXT // num_model

    def body(i, v):
        rel = i - jax.lax.axis_index("model") * f_local
        inside = ((rel >= 0) & (rel < f_local)).astype(v.dtype)
        part, plane = text_gram(
            jnp.clip(rel, 0, f_local - 1), v * inside, f_local,
            feature_axis="model")
        return part[None], plane[None]

    parts, planes = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P("model"), P("model")),
    ))(idx, val)
    return np.asarray(parts), np.asarray(planes)


@pytest.mark.parametrize("num_model", [2, 4])
@pytest.mark.parametrize("plane, index", [
    ("s8", 2), ("bf16_rung1", 1), ("bf16_rung2", 1), ("exact", 0),
    ("fractional", 0),
])
def test_shard_panels_sum_to_the_unsharded_gram(plane, index, num_model):
    """Σ over the model shards of the partial G is the unsharded G, integer
    for integer on the integer planes, and every shard reports the plane
    the WHOLE row's figures ask for — a slice alone would often pass a
    faster rung (200 of mass in halves is 100 a slice: s8)."""
    idx, val = _plane_batch(plane)
    whole, took = text_gram(idx, val, F_TEXT)
    assert int(took) == index
    parts, planes = _shard_panels(idx, val, num_model)
    assert planes.tolist() == [index] * num_model
    total = parts.astype(np.float64).sum(axis=0)
    if plane == "fractional":
        np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-6)
    else:
        np.testing.assert_array_equal(total, np.asarray(whole, np.float64))


def test_global_row_mass_over_the_bound_sends_every_shard_to_exact():
    """512 distinct features of mass 256 each in one row, half in each
    slice: every (row, feature) mass is 256 and each SLICE's row mass is
    65,536, inside rung 2's bounds — a shard on its own would take bf16 —
    but the row's mass is 131,072, so 256 · mass passes 2^24 and the psum
    of the partial Gs is no longer provably exact in f32: all shards take
    ``exact``, as the unsharded gate does."""
    b, slots, f_local = 4, 512, F_TEXT // 2
    idx = np.zeros((b, slots), np.int32)
    idx[:, :256] = np.arange(256)                  # slice 0
    idx[:, 256:] = f_local + np.arange(256)        # slice 1
    val = np.full((b, slots), 256.0, np.float32)
    idx, val = jnp.asarray(idx), jnp.asarray(val)
    for shard in range(2):   # each slice alone: bf16 by rung 2
        rel = idx - shard * f_local
        inside = ((rel >= 0) & (rel < f_local)).astype(val.dtype)
        _, alone = text_gram(jnp.clip(rel, 0, f_local - 1), val * inside,
                             f_local)
        assert int(alone) == 1
    assert int(text_gram(idx, val, F_TEXT)[1]) == 0
    parts, planes = _shard_panels(idx, val, 2)
    assert planes.tolist() == [0, 0]
    np.testing.assert_allclose(
        parts.astype(np.float64).sum(axis=0),
        np.full((b, b), 512 * 256.0 * 256.0))


def test_step_delivers_the_plane_all_shards_took():
    """Through the mesh: one feature 257 times in one row sends the whole
    step to ``exact``; the index leaves with the stats, the weights are the
    single-device step's."""
    idx, val = _plane_batch("exact")
    rows = idx.shape[0]
    batch = FeatureBatch(
        np.asarray(idx), np.asarray(val), np.zeros((rows, 4), np.float32),
        np.full(rows, 300.0, np.float32), np.ones(rows, np.float32))
    model = ParallelSGDModel(_mesh(), quality=True, **KW)
    single = StreamingLinearRegressionWithSGD(quality=True, **KW)
    q = np.asarray(model.step(batch).quality)
    assert q[QUALITY_INDEX["gram_plane"]] == 0.0
    single.step(batch)
    np.testing.assert_allclose(
        model.latest_weights, single.latest_weights, rtol=2e-5, atol=2e-6)


# ---- (iv) save sharded, serve flat, resume on one device, and back --------

def _checkpointed(tmp_path, name, model, totals):
    conf = ConfArguments().parse([
        "--checkpointDir", str(tmp_path / name),
        "--numTextFeatures", str(F_TEXT)])
    return AppCheckpoint(conf, lambda: model.latest_weights,
                         model.set_initial_weights, totals)


@pytest.mark.parametrize("layout", [(2, 2), (1, 4)],
                         ids=lambda l: f"{l[0]}x{l[1]}")
def test_checkpoint_sharded_to_servable_to_one_device_and_back(
    tmp_path, layout
):
    from twtml_tpu.serving import load_servable

    batch = FeatureBatch(*(np.asarray(a) for a in (
        *_plane_batch("s8"), np.zeros((16, 4), np.float32),
        np.full(16, 300.0, np.float32), np.ones(16, np.float32))))
    model = ParallelSGDModel(_mesh(*layout), **KW)
    model.set_initial_weights(_seeded_weights())
    model.step(batch)
    totals = {"count": 16, "batches": 1}
    assert _checkpointed(tmp_path, "a", model, dict(totals)).save_now(totals)
    live = model.latest_weights
    crc = state_checksum(live)

    # the serving plane loads the flat vector on one device
    snapshot, reason = load_servable(str(tmp_path / "a"))
    assert snapshot is not None, reason
    flat = np.asarray(snapshot.weights)
    assert flat.shape == (F_TEXT + 4,) and state_checksum(flat) == crc

    # a one-device run resumes from the sharded run's checkpoint ...
    single = StreamingLinearRegressionWithSGD(**KW)
    totals1 = {"count": 0, "batches": 0}
    _checkpointed(tmp_path, "a", single, totals1)
    assert totals1 == totals
    np.testing.assert_array_equal(single.latest_weights, live)
    # ... trains on, saves, and a sharded run resumes from THAT
    single.step(batch)
    totals1 = {"count": 32, "batches": 2}
    assert _checkpointed(tmp_path, "b", single, dict(totals1)).save_now(totals1)
    resumed = ParallelSGDModel(_mesh(*layout), **KW)
    totals2 = {"count": 0, "batches": 0}
    ckpt = _checkpointed(tmp_path, "b", resumed, totals2)
    assert totals2 == totals1
    assert resumed._weights["text"].sharding.spec == P("model")
    np.testing.assert_array_equal(
        resumed.latest_weights, single.latest_weights)
    # both go on to the same weights
    model.step(batch)
    np.testing.assert_allclose(
        model.latest_weights, resumed.latest_weights, rtol=2e-5, atol=2e-6)

    # the divergence sentinel's rollback: poisoned weights back to the
    # verified archive, on the pytree
    resumed.set_initial_weights(np.full(F_TEXT + 4, np.nan, np.float32))
    assert ckpt.rollback_to_verified()["batches"] == 2
    np.testing.assert_array_equal(
        resumed.latest_weights, single.latest_weights)


# ---- (v) module name, stage names and the collective scope ----------------

F_BIG = 1 << 20   # hash2e20's width; 8 rows, nothing runs


def _wire(form: str, model, rows: int, row_len: int):
    lens = np.full(rows, row_len // 2, np.int32)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    rb = RaggedUnitBatch(
        np.zeros(int(offsets[-1]), np.uint16), offsets,
        np.zeros((rows, 4), np.float32), np.zeros(rows, np.float32),
        np.ones(rows, np.float32), row_len)
    if form == "packed":
        return model.pack_for_wire(rb)
    if form == "ragged":
        return model.prepare(rb)
    if form == "units":
        return UnitBatch(
            np.zeros((rows, row_len), np.uint16), np.zeros(rows, np.int32),
            rb.numeric, rb.label, rb.mask)
    return FeatureBatch(
        np.zeros((rows, row_len), np.int32),
        np.zeros((rows, row_len), np.float32), rb.numeric, rb.label, rb.mask)


def _lowered(form="packed", layout=(2, 2), rows=8, row_len=16):
    model = ParallelSGDModel(
        _mesh(*layout), num_text_features=F_BIG, num_iterations=50,
        step_size=0.005, l2_reg=0.1, quality=True)
    wire = _wire(form, model, rows, row_len)
    return model._step_for(type(wire)).lower(model._weights, wire)


@pytest.mark.parametrize("form, layout, rows, row_len", [
    ("packed", (2, 2), 8, 16), ("packed", (2, 2), 16, 32),
    ("packed", (1, 4), 8, 16), ("packed", (4, 1), 8, 16),
    ("ragged", (2, 2), 8, 16), ("units", (2, 2), 8, 16),
    ("hashed", (2, 2), 8, 16),
])
def test_sharded_step_module_name_is_the_same_for_every_bucket_and_wire(
    form, layout, rows, row_len
):
    """``jit_sharded_train_step`` whatever the bucket, the wire form or the
    layout: the device plane's ``XLA Modules`` line and the ``compile``
    spans' ``fun`` name the mesh step one way."""
    text = _lowered(form, layout, rows, row_len).as_text()
    assert re.search(
        r"^module @(\S+)", text, re.M).group(1) == "jit_sharded_train_step"


@pytest.mark.parametrize("layout", [(4, 1), (2, 2)])
def test_mesh_model_compiles_one_train_program(layout):
    """``jit(sharded_train_step)`` is the ONLY train program a mesh model
    ever compiles, on the 1-D layout and the model-sharded ones alike —
    once per (wire form, bucket), and nothing else that steps or scans the
    weights: the model has one step surface."""
    from test_step_scopes import WIRES, compiled_funs

    # both buckets of the wire the cells ship, one of each other form
    wires = [w for w in WIRES if w[0] == "packed" or w[1] == 8]

    model = ParallelSGDModel(
        _mesh(*layout), num_text_features=1 << 12, num_iterations=2)

    def run():
        for form, rows, row_len in wires:
            for _ in range(2):  # the repeat is served by jit's cache
                model.step(_wire(form, model, rows, row_len))

    funs = compiled_funs(run)
    train = [f for f in funs if "step" in f or "scan" in f]
    assert set(train) == {"jit(sharded_train_step)"}, funs
    # one compile per (form, bucket) — and one more for the model's second
    # step ever: its freshly built weights carry no mesh sharding yet, the
    # first step's output does (PERF.md §7)
    assert len(wires) <= len(train) <= len(wires) + 1, funs
    assert [n for n in dir(model) if "many" in n or "scan" in n] == []


@pytest.fixture(scope="module")
def op_names():
    """The op-name paths of the COMPILED module (each HLO instruction's
    ``op_name`` metadata, what the profiler hands ``stage_times``), at
    hash2e20's width on the 2 x 2 mesh, the per-shard packed ragged wire
    the cell runs. The lowered text will not do here: inside ``shard_map``
    its locations are relative to the body."""
    return set(re.findall(
        r'op_name="(jit\(sharded_train_step\)[^"]*)"',
        _lowered().compile().as_text()))


@pytest.mark.parametrize("scope", STAGE_SCOPES)
def test_sharded_step_carries_the_single_device_stage_names(op_names, scope):
    assert any(f"/{scope}/" in n or n.endswith(f"/{scope}")
               for n in op_names), scope


@pytest.mark.parametrize("stage, collective", [
    ("predict", "psum"), ("hash", "all_gather"), ("predict", "all_gather"),
    ("gram_count", "psum"), ("gram_count", "pmin"), ("gram_matmul", "psum"),
    ("gram_matmul", "all_gather"), ("gram_matmul", "pmin"),
    ("writeback", "psum"), ("quality", "psum"),
])
def test_each_collective_is_under_collective_inside_its_stage(
    op_names, stage, collective
):
    assert any(re.search(rf"/{stage}/(?:[^/]*/)*collective/{collective}", n)
               for n in op_names), (stage, collective)


def test_no_collective_of_the_sharded_step_is_outside_the_scope():
    """Every all-reduce / all-gather instruction of the compiled step
    carries ``collective`` on its op-name path: the reader that sums the
    collectives' device time by that name misses none."""
    found = re.findall(
        r"= [^=\n]*? (all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\([^\n]*op_name=\"([^\"]*)\"",
        _lowered().compile().as_text())
    assert len(found) >= 6
    for kind, path in found:
        assert "/collective/" in path, (kind, path)


def test_sharded_step_has_no_scope_name_before_one_of_the_nine(op_names):
    """``stage_times`` gives a path to the FIRST of the nine names on it: a
    scope of another name ahead of them would fall to ``other`` in silence.
    ``collective`` is always UNDER one of the nine, so the stages' sum is
    still the step's device time and the collectives' time is a part of
    it, not beside it."""
    known = set(STAGE_SCOPES)
    for name in op_names:
        for part in name.split("/")[1:-1]:
            if part in known:
                break
            if re.fullmatch(r"[a-z_]+", part):
                assert part in (
                    "cond", "while", "body", "shard_map", "closed_call"
                ), name


# ---- (vi) PR 28: the mesh steps predict and write back through C ----------

@pytest.mark.parametrize("layout", [(2, 2), (1, 4), (4, 1)],
                         ids=lambda l: f"{l[0]}x{l[1]}")
def test_sharded_gram_step_asks_for_no_gather_or_scatter_on_its_weights(
    layout
):
    """Counted on the lowered per-shard program at hash2e20's width, for
    the 2-D step and (4 x 1) the data-only mesh step: inside the Gram basis
    no gather reads the shard's ``[F_local]`` text weights and no scatter
    writes an array of that shape — both went through ``sparse_text_dot`` /
    ``sparse_grad_text`` until PR 28 (tests/test_step_scopes.py counts the
    compiled branches, and what a count of this kind finds in the scatter
    loop)."""
    from test_step_scopes import gathers_and_scatters

    text = _lowered("packed", layout).as_text()
    weights = f"tensor<{F_BIG // layout[1]}xf32>"
    assert weights in text  # the shard's slice is an array of the program
    gathers, scatters = gathers_and_scatters(text)
    assert gathers  # the ragged wire's re-pad still gathers units
    assert weights not in gathers and weights not in scatters


# ---- (vii) PR 30: C keeps the shape its build writes; the collectives stay --

# the lowered per-shard program's collectives as the parent of PR 30 had
# them (packed ragged wire, 8 rows of 16, hash2e20's width): per layout
# {(op, operand type): count}; the three plane branches each hold the body's
_PARENT_COLLECTIVES = {
    (2, 2): {
        ("all_gather", "4x15xf32"): 1, ("all_gather", "4x15xi32"): 1,
        ("all_gather", "4x4xf32"): 1, ("all_gather", "4x8xf32"): 3,
        ("all_gather", "4xf32"): 5,
        ("all_reduce.add", "32xf32"): 1, ("all_reduce.add", "4x8xf32"): 3,
        ("all_reduce.add", "4xf32"): 8, ("all_reduce.add", "524288xf32"): 3,
        ("all_reduce.add", "8xf32"): 1, ("all_reduce.add", "f32"): 26,
        ("all_reduce.minimum", "2xi32"): 1, ("all_reduce.minimum", "i32"): 2,
    },
    (1, 4): {
        ("all_gather", "8x15xf32"): 1, ("all_gather", "8x15xi32"): 1,
        ("all_gather", "8x4xf32"): 1, ("all_gather", "8x8xf32"): 3,
        ("all_gather", "8xf32"): 5,
        ("all_reduce.add", "262144xf32"): 3, ("all_reduce.add", "32xf32"): 1,
        ("all_reduce.add", "4xf32"): 5, ("all_reduce.add", "8x8xf32"): 3,
        ("all_reduce.add", "8xf32"): 4, ("all_reduce.add", "f32"): 26,
        ("all_reduce.minimum", "2xi32"): 1, ("all_reduce.minimum", "i32"): 2,
    },
    (4, 1): {
        ("all_gather", "2x15xf32"): 1, ("all_gather", "2x15xi32"): 1,
        ("all_gather", "2x4xf32"): 1, ("all_gather", "2x8xf32"): 3,
        ("all_gather", "2xf32"): 5,
        ("all_reduce.add", "1048576xf32"): 3, ("all_reduce.add", "32xf32"): 1,
        ("all_reduce.add", "4xf32"): 5, ("all_reduce.add", "f32"): 21,
        ("all_reduce.minimum", "i32"): 1,
    },
}
_ALL_GATHER = re.compile(
    r'"stablehlo\.all_gather"\([^\n]*? : \(tensor<([^>]*)>\) -> ')
_ALL_REDUCE = re.compile(
    r'"stablehlo\.all_reduce"\([\s\S]*?stablehlo\.(add|minimum|maximum)'
    r'[\s\S]*?\}\) : \(tensor<([^>]*)>\) -> ')


@pytest.mark.parametrize("layout", sorted(_PARENT_COLLECTIVES),
                         ids=lambda l: f"{l[0]}x{l[1]}")
def test_mesh_step_collectives_are_the_parents(layout):
    """Contracting C as built changes what a shard computes between its
    collectives and none of them: the same all-gathers and all-reduces, of
    the same operands, as before — the predict psum still carries
    ``[B_local]`` (``dot`` slices its ``[B]`` before the psum), the
    write-back psum still ``[F_local]`` (``tdot`` flattens and crops before
    it) — and no other kind of collective."""
    from collections import Counter

    text = _lowered("packed", layout).as_text()
    found = Counter(("all_gather", op) for op in _ALL_GATHER.findall(text))
    found.update((f"all_reduce.{kind}", op)
                 for kind, op in _ALL_REDUCE.findall(text))
    assert found == _PARENT_COLLECTIVES[layout]
    assert sum(found.values()) == len(re.findall(
        r"stablehlo\.(?:all_|reduce_scatter|collective_)", text))
