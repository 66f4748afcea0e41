"""A champion and its challengers on the SAME rows, on the 2 x 2 mesh
(``--tenants M --tenantKey all --modelShards 2``; PR 52, configuration
``hash2e20-grid4``), at sizes the virtual CPU mesh holds:

(a) the arm law on a mesh, over three batches from seeded weights, on each
    Gram plane the gate can take: the mesh arms' step against (i) the
    one-device arms' step and (ii) M single-model 2 x 2 steps, each under
    its own recipe, and all three against the plain float64 reference
    (``benchmark/reference/grid_linear_sgd.py``), whose bf16 control fails
    the same limit; recipes swapped, or all made arm 0's, fail it too;
(b) through the app (block ingest, ragged wire, ``FetchPipeline``): the
    stacked ``[M, F+4]`` verified checkpoint against the reference and the
    one-device run, the trace's instants, one fetch a batch;
(c) the checkpoint written from the mesh resumes on the mesh bit for bit,
    is refused under another tenant list, resumes on one device and loads
    in ``serving/abtest.py``;
(d) each composition that stays refused still says its sentence.

The compiled program (C and G once, ``hash2e20``'s collectives in number)
is ``tests/test_step_scopes.py``'s ``2x2-arms`` case; the harness-level
fault cases of the cell are ``benchmark/tests/test_hash2e20_grid4.py``.
"""

from __future__ import annotations

import json
import logging

import numpy as np
import pytest

import jax

from benchmark import gen, manifest
from benchmark import spans as span_files
from benchmark.reference import grid_linear_sgd as ref
from test_tenant_deployment import _run_app, _stream, _weights
from test_tenant_deployment import F_TEXT as APP_F_TEXT
from test_tenant_grid import GRID, GRID_MODEL, L2S, STEPS
from twtml_tpu.config import ConfArguments
from twtml_tpu.ops.quality import QUALITY_INDEX
from twtml_tpu.telemetry import metrics as _metrics
from twtml_tpu.telemetry import tenants as _tenants_tel

F_TEXT = 1 << 14
ROWS, BATCHES = 64, 3
MESH = ["--master", "local[4]", "--modelShards", "2"]
# Σ|w − w_ref| ÷ Σ|w_ref| over the whole [4, F+4] array, float32 against
# float64 after three batches from weights of scale 0.3: every path here
# reads 2e-8 to 4e-8 (the weights are mostly the seeded ones, scaled: the
# rounding of three batches' updates). The bf16 control reads 1e-4 and more
# at this size, swapped recipes 1e-2: 2e-6 stands 50x clear of both sides.
WEIGHTS_DEV_LIMIT = 2e-6
PLANES = {"exact": 0, "bf16": 1, "s8": 2}


@pytest.fixture(autouse=True)
def _fresh_registries():
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()


def _mesh():
    from twtml_tpu.parallel import make_mesh

    return make_mesh(num_data=2, num_model=2, devices=jax.devices()[:4])


def _dev(w, r) -> float:
    return float(np.abs(w - r).sum() / np.abs(r).sum())


def _stream_for(plane: str, seed: int = 11):
    """``(generator, truth columns, ragged batches)`` of ROWS x BATCHES
    seeded tweets that send every batch to ``plane``: texts of at most 100
    units (every row's mass ≤ 127: s8), the 280-unit mix as it stands (rows
    over 127 bigrams: bf16), and that mix with one tweet a batch made of
    ONE character 270 times (one bigram 269 times in a row: exact)."""
    from twtml_tpu.features.featurizer import Featurizer, Status

    g = dict(manifest.load_json(
        manifest.traffic_path("trimmed-kept-280"))["generator"])
    g.update(pool_lines=ROWS * BATCHES, length_block=ROWS, vocab_size=2000)
    if plane == "s8":
        g.update(text_units_max=100, text_units_mean=70, text_units_sd=20)
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                           ROWS * BATCHES)
    lines = [json.loads(line) for line in chunk.lines]
    if plane == "exact":
        for b in range(BATCHES):
            chunk.text[b * ROWS] = "k" * 270
            lines[b * ROWS]["retweeted_status"]["text"] = "k" * 270
    feat = Featurizer(now_ms=g["now_ms"], num_text_features=F_TEXT)
    statuses = [Status.from_json(d) for d in lines]
    batches = [
        feat.featurize_batch_ragged(
            statuses[b * ROWS:(b + 1) * ROWS], row_bucket=ROWS,
            pre_filtered=True)
        for b in range(BATCHES)
    ]
    return g, chunk, batches


def _reference(g, chunk, w0, recipes, precision="float64"):
    learner = ref.GridLinearSGD(F_TEXT, recipes, num_iterations=50,
                                precision=precision)
    for arm, w in zip(learner.arms, w0):
        arm.w = w.astype(np.float64)
    cols = (chunk.followers, chunk.favourites, chunk.friends,
            chunk.created_ms, chunk.retweets)
    stats = []
    for b in range(BATCHES):
        s = slice(b * ROWS, (b + 1) * ROWS)
        stats.append(learner.step_batch(
            chunk.text[s], *(np.asarray(c)[s] for c in cols),
            now_ms=g["now_ms"]))
    return learner, stats


# ---------------------------------------------------------------------------
# (a) the arm law on a mesh

@pytest.mark.parametrize("plane", sorted(PLANES))
def test_mesh_arms_are_the_one_device_arms_and_m_single_mesh_models(plane):
    """Weights and every leaf of the fetched output after each of three
    batches. Against the M single-model 2 x 2 steps the tolerance is
    float32 ROUNDING — the arms' program is the single model's with its
    per-arm sums stacked, so only the order of an f32 sum may differ:
    rtol 2e-5 / atol 2e-6 on the weights (what
    tests/test_feature_sharded_step.py allows one layout against another;
    the CPU backend in fact gives every leaf bit for bit) and the batch
    statistics to 1e-6 relative. Against the one-device arms the layout
    differs (the text sums are split over two slices and psummed), the same
    tolerance. All of them sit ``WEIGHTS_DEV_LIMIT`` from the float64
    reference, where its bf16 control and any mix-up of the recipes do
    not."""
    from twtml_tpu.parallel import ParallelSGDModel, TenantStackModel

    g, chunk, batches = _stream_for(plane)
    kw = dict(num_text_features=F_TEXT, num_iterations=50)
    rng = np.random.default_rng(3)
    w0 = (rng.normal(size=(4, F_TEXT + 4)) * 0.3).astype(np.float32)
    mesh = _mesh()
    arms = ParallelSGDModel(mesh, quality=True, arms=(STEPS, L2S), **kw)
    assert (arms.num_tenants, arms.tenant_key, arms.shared_rows) == (
        4, "all", True)
    one = TenantStackModel(4, tenant_key="all", step_sizes=STEPS,
                           l2_regs=L2S, quality=True, use_sparse=True,
                           use_gram=True, **kw)
    singles = [ParallelSGDModel(mesh, quality=True, step_size=s, l2_reg=r,
                                **kw) for s, r in zip(STEPS, L2S)]
    arms.set_initial_weights(w0)
    one.set_initial_weights(w0)
    for single, w in zip(singles, w0):
        single.set_initial_weights(w)
    learner, stats = _reference(g, chunk, w0, list(zip(STEPS, L2S)))

    for rb, ref_stats in zip(batches, stats):
        out = jax.device_get(arms.step(arms.pack_for_wire(rb)))
        assert out.predictions.shape == (4, ROWS)
        assert out.quality[:, QUALITY_INDEX["gram_plane"]].tolist() == (
            [PLANES[plane]] * 4)
        out1 = jax.device_get(one.step(one.pack_for_wire(rb)))
        alone = [jax.device_get(s.step(s.pack_for_wire(rb)))
                 for s in singles]
        for name in ("count", "mse", "real_stdev", "pred_stdev"):
            got = np.asarray(getattr(out, name))
            np.testing.assert_allclose(
                got, np.asarray(getattr(out1, name)), rtol=1e-6)
            np.testing.assert_allclose(
                got, [float(getattr(a, name)) for a in alone], rtol=1e-6)
        # predictions are HALF_UP integers: a margin within float32
        # rounding of a half may round the other way on another layout
        for other in (out1.predictions, np.stack(
                [a.predictions for a in alone])):
            assert np.abs(out.predictions - other).max() <= 1.0
            assert (out.predictions != other).mean() <= 0.01
        np.testing.assert_allclose(
            out.quality, np.stack([a.quality for a in alone]),
            rtol=1e-5, atol=1e-5)
        assert float(out.count[0]) == ref_stats["count"] == ROWS
        for m in range(4):
            assert abs(float(out.mse[m]) - ref_stats["arm_mse"][m]) <= (
                2e-4 * ref_stats["arm_mse"][m] + 1)

    w = arms.latest_weights
    assert w.shape == (4, F_TEXT + 4) and w.dtype == np.float32
    np.testing.assert_allclose(w, one.latest_weights, rtol=2e-5, atol=2e-6)
    for m, single in enumerate(singles):
        np.testing.assert_allclose(
            w[m], single.latest_weights, rtol=2e-5, atol=2e-6)
    # four different models came out, each the reference's
    assert len({w[m].tobytes() for m in range(4)}) == 4
    for got in (w, one.latest_weights,
                np.stack([s.latest_weights for s in singles])):
        assert _dev(got, learner.w) < WEIGHTS_DEV_LIMIT
    # ... which the recipes swapped, every arm given arm 0's, and the
    # reference's own bf16 control all fail
    swapped, _ = _reference(g, chunk, w0, list(zip(
        [STEPS[i] for i in (0, 2, 1, 3)], [L2S[i] for i in (0, 2, 1, 3)])))
    champion, _ = _reference(g, chunk, w0, [(STEPS[0], L2S[0])] * 4)
    control, _ = _reference(g, chunk, w0, list(zip(STEPS, L2S)), "bf16")
    for wrong in (swapped, champion, control):
        assert _dev(w, wrong.w) > 5 * WEIGHTS_DEV_LIMIT


def test_the_mesh_model_takes_and_gives_the_stacked_state():
    """``latest_weights`` / ``set_initial_weights`` speak ``[M, F+4]`` (the
    checkpoint's layout), bit for bit through the sharded pytree; a flat
    vector is every arm's (the sentinel's zeros-reset); another M is
    refused; the ``mesh_arms`` figures are the configuration's."""
    from twtml_tpu.parallel import ParallelSGDModel

    model = ParallelSGDModel(_mesh(), num_text_features=F_TEXT,
                             arms=(STEPS, L2S))
    assert model.latest_weights.shape == (4, F_TEXT + 4)
    assert not model.latest_weights.any()
    rng = np.random.default_rng(5)
    w = rng.normal(size=(4, F_TEXT + 4)).astype(np.float32)
    assert model.set_initial_weights(w).latest_weights.tobytes() == (
        w.tobytes())
    assert model._weights["text"].shape == (4, F_TEXT)
    assert model._weights["text"].sharding.shard_shape(
        (4, F_TEXT)) == (4, F_TEXT // 2)
    model.set_initial_weights(w[1])
    assert (model.latest_weights == w[1]).all()
    with pytest.raises(ValueError, match="lead with 3 tenants"):
        model.set_initial_weights(w[:3])
    assert model.mesh_arms(2048) == {
        "arms": 4, "data": 2, "model": 2,
        "u_gather_bytes": 4 * 1024 * 4,
        "delta_psum_bytes": 4 * (F_TEXT // 2 + 4) * 4}
    plain = ParallelSGDModel(_mesh(), num_text_features=F_TEXT)
    assert plain.mesh_arms(2048) is None
    assert not hasattr(plain, "num_tenants")


# ---------------------------------------------------------------------------
# (b) through the app

def _events(path, name):
    return [e for e in span_files.load_events(path) if e.get("name") == name]


def test_through_the_app_on_the_mesh_against_reference_and_one_device(
        tmp_path, monkeypatch):
    """``apps.linear_regression.run`` with the configuration's flags at CPU
    sizes: four batches on the 2 x 2 mesh through the normal path. The
    ``[4, F+4]`` checkpoint against the float64 reference (the limit of
    tests/test_tenant_grid.py: 5e-6, zero weights to start with), the
    recipes swapped or all arm 0's far over it, the one-device run of the
    same command line to float32 rounding; the printed lines are the
    champion's; ONE fetch and ONE upload a batch; the trace says the mesh,
    what the arms ship and, per batch, ``tenant_rows`` with the ``[d, m]``
    added."""
    rows, batches = 64, 4
    g, chunk, path = _stream(tmp_path, rows, batches, 7)
    trace = str(tmp_path / "mesh.spans.json")
    calls, real = [], jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: (calls.append(1), real(x))[1])
    totals, printed = _run_app(
        monkeypatch, path, str(tmp_path / "mesh"), rows, batches,
        GRID + MESH + ["--trace", trace])
    monkeypatch.setattr(jax, "device_get", real)
    assert totals["batches"] == batches and totals["tenants"] == 4
    assert totals["device_span"] == {"weights": 4, "batch": 4}
    assert _metrics.get_registry().counter(
        "tenants.shared_batches").snapshot() == batches
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches, model=GRID_MODEL,
        generator=g)
    assert [p["batch"] for p in printed] == [rows] * batches
    for p, s in zip(printed, stats):
        assert abs(p["mse"] - s["mse"]) <= 2e-4 * s["mse"]
    w = _weights(str(tmp_path / "mesh"))
    assert w.shape == learner.w.shape == (4, APP_F_TEXT + 4)
    assert _dev(w, learner.w) < 5e-6
    assert _dev(w[[0, 2, 1, 3]], learner.w) > 1e-3
    assert _dev(np.broadcast_to(w[0], w.shape), learner.w) > 1e-3
    _t, printed1 = _run_app(monkeypatch, path, str(tmp_path / "one"), rows,
                            batches, GRID)
    # the champion's line on both layouts: at 64 rows ONE prediction that
    # float32 rounds the other way moves a batch's mse by ~10 units
    assert [p["count"] for p in printed1] == [p["count"] for p in printed]
    for p, q in zip(printed, printed1):
        assert abs(p["mse"] - q["mse"]) <= 2e-4 * q["mse"]
    np.testing.assert_allclose(
        w, _weights(str(tmp_path / "one")), rtol=2e-5, atol=2e-6)

    assert len(calls) == batches                       # one fetch a batch
    assert len(_events(trace, "wire_pack")) == len(
        _events(trace, "dispatch")) == batches
    assert not _events(trace, "tenant_split")
    (layout,) = _events(trace, "mesh_layout")
    assert layout["args"] == {"data": 2, "model": 2,
                              "f_text_local": APP_F_TEXT // 2, "devices": 4}
    (shipped,) = _events(trace, "mesh_arms")
    assert shipped["args"] == {
        "arms": 4, "data": 2, "model": 2,
        "u_gather_bytes": 4 * (rows // 2) * 4,
        "delta_psum_bytes": 4 * (APP_F_TEXT // 2 + 4) * 4}
    routed = _events(trace, "tenant_rows")
    assert len(routed) == batches
    for e in routed:
        a = e["args"]
        assert (a["key"], a["rows"], a["mesh"]) == ("all", [rows] * 4, [2, 2])
        assert a["bucket"] == rows and a["pad_rows"] == 0
    planes = _events(trace, "gram_plane")
    assert len(planes) == batches
    assert all(e["args"]["plane"] >= 1 for e in planes)
    compiled = {e["args"]["fun"] for e in _events(trace, "compile")}
    assert "jit(sharded_train_step)" in compiled
    assert not {"jit(shared)", "jit(train_step)"} & compiled


# ---------------------------------------------------------------------------
# (c) the checkpoint

def _crc_lines(run):
    seen = []
    handler = logging.Handler()
    handler.emit = lambda rec: seen.append(rec.getMessage())
    logger = logging.getLogger("twtml_tpu.apps.common")
    logger.addHandler(handler)
    try:
        return run(), seen
    finally:
        logger.removeHandler(handler)


def test_the_mesh_checkpoint_resumes_is_refused_and_serves(
        tmp_path, monkeypatch):
    from twtml_tpu.apps.common import state_checksum
    from twtml_tpu.checkpoint import Checkpointer
    from twtml_tpu.serving import load_servable
    from twtml_tpu.serving.abtest import ChampionEngine

    rows, batches = 64, 3
    _g, _chunk, path = _stream(tmp_path, rows, batches, 7)
    ckpt = str(tmp_path / "ck")
    _run_app(monkeypatch, path, ckpt, rows, batches, GRID + MESH)
    assert Checkpointer(ckpt).latest_meta()["tenants"] == {
        "count": 4, "key": "all", "stepSize": STEPS, "l2Reg": L2S}
    snapshot, reason = load_servable(ckpt)
    assert snapshot is not None, reason
    before = np.asarray(snapshot.weights)
    assert before.shape == (4, APP_F_TEXT + 4) and before.dtype == np.float32
    crc = state_checksum(before)

    # PR 47's rule holds on the mesh: other lists, another key, no lists
    for other in (
        GRID[:5] + ["0.005,0.005,0.005,0.005"] + GRID[6:] + MESH,
        ["--tenants", "4", "--tenantKey", "all"] + MESH,
        ["--tenants", "4", "--tenantKey", "hash", "--master", "local[4]"],
    ):
        with pytest.raises(SystemExit, match="per-tenant lists"):
            _run_app(monkeypatch, path, ckpt, rows, batches, other)

    # on the mesh again: the restored state is the archive's, bit for bit
    # (the crc the restore logs), and with no batch left in the file the
    # final save writes the same [4, F+4] back through the sharded pytree
    (totals, _printed), seen = _crc_lines(lambda: _run_app(
        monkeypatch, path, ckpt, rows, batches + 1, GRID + MESH))
    assert any(f"state crc {crc}" in m for m in seen), seen
    assert (totals["batches"], totals["count"]) == (batches, rows * batches)
    after, _reason = load_servable(ckpt)
    assert np.asarray(after.weights).tobytes() == before.tobytes()
    # and on ONE device: the stacked checkpoint is the plane's own
    (_totals, _p), seen = _crc_lines(lambda: _run_app(
        monkeypatch, path, ckpt, rows, batches + 1, GRID))
    assert any(f"state crc {crc}" in m for m in seen), seen

    # serving/abtest.py installs it: four variants, the recipes reported
    engine = ChampionEngine(num_text_features=APP_F_TEXT, num_tenants=4,
                            tenant_key="hash")
    engine.set_snapshot(after)
    view = engine.abtest_view()
    assert view["tenantKey"] == "all" and view["champion"] in range(4)
    assert [(s["tenant"], s["stepSize"], s["l2Reg"])
            for s in view["shadows"]] == list(zip(range(4), STEPS, L2S))


# ---------------------------------------------------------------------------
# (d) what stays refused, in its words

def _build(*flags):
    from twtml_tpu.apps.common import build_model

    return build_model(ConfArguments().parse(
        ["--backend", "cpu", "--numTextFeatures", str(F_TEXT), *flags]))


@pytest.mark.parametrize("flags, said", [
    # a partitioning key with a model axis: build_mesh's sentence
    (["--tenants", "4", "--tenantKey", "hash"] + MESH,
     "not for tenant plane"),
    (["--tenants", "4", "--tenantKey", "lang", "--hashOn", "device"] + MESH,
     "not for tenant plane"),
    # ``all`` on the data-only mesh
    (GRID + ["--master", "local[4]"], "data-only mesh has no form"),
    (GRID + ["--master", "local[4]"], "one device"),
    # ``all`` on the group wire, on one device and on the mesh
    (GRID + ["--master", "local[1]", "--wirePack", "group"],
     "--wirePack group"),
    # a model axis that does not divide
    (GRID + ["--master", "local[4]", "--modelShards", "3"], "must divide"),
])
def test_each_composition_that_stays_refused_says_its_sentence(flags, said):
    with pytest.raises(SystemExit) as exc:
        _build(*flags)
    assert said in str(exc.value)


def test_all_across_hosts_is_refused_before_any_mesh(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(SystemExit, match="single-host"):
        _build(*GRID, *MESH)


def test_the_library_classes_refuse_what_has_no_form():
    from twtml_tpu.parallel import (
        ParallelSGDModel,
        TenantStackModel,
        make_mesh,
    )

    data_only = make_mesh(num_data=4, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="data-only mesh has no form"):
        ParallelSGDModel(data_only, num_text_features=F_TEXT,
                         arms=(STEPS, L2S))
    with pytest.raises(ValueError, match="one step size and one L2"):
        ParallelSGDModel(_mesh(), num_text_features=F_TEXT,
                         arms=(STEPS, L2S[:2]))
    # the one-device stack names the class that runs them on a mesh
    with pytest.raises(ValueError, match=r"ParallelSGDModel\(arms="):
        TenantStackModel(4, num_text_features=F_TEXT, tenant_key="all",
                         mesh=_mesh())
    # outside the Gram basis (float64 weights never take it) there is no
    # arms step on a mesh
    import jax.numpy as jnp

    _g, _chunk, batches = _stream_for("bf16")
    model = ParallelSGDModel(_mesh(), num_text_features=F_TEXT,
                             arms=(STEPS, L2S), use_gram=False)
    with pytest.raises(ValueError, match="Gram basis only"):
        model.step(model.pack_for_wire(batches[0]))
    assert jnp.float32 == model.dtype


def test_the_group_wire_is_refused_on_the_mesh_too():
    with pytest.raises(SystemExit) as exc:
        _build(*GRID, *MESH, "--wirePack", "group")
    assert "--wirePack group" in str(exc.value)
