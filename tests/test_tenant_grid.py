"""A champion and its challengers on the SAME rows (``--tenants M
--tenantKey all`` with ``--tenantStepSize`` / ``--tenantL2Reg``; PR 47,
configuration ``hash2e18-grid4``), at sizes a CPU holds:

(a) the arm law: each arm's weights and stats are the single model's under
    that arm's recipe on the same stream — bit for bit in the scatter loop
    and on the dense path; in the Gram basis (one count matrix and one G a
    batch, C read once for ALL arms by each of its two contractions, only
    the dual loop mapped: PR 50) to float32 rounding, which on the CPU
    backend is: every output leaf and the text weights bit for bit, the
    four numeric weights within 2 ulp; and through the app;
(b) the program against its plain reference
    (``benchmark/reference/grid_linear_sgd.py``), and the reference's bf16
    control against the same limit;
(c) the batch's line is the champion's with ``count`` = B, and a NaN in one
    challenger alone reaches the sentinel;
(d) the wire: the single model's bytes, once; one fetch; no split;
(e) what the key refuses, each with its reason;
(f) the checkpoint names the key and each arm's recipe, a resume under
    other lists is refused, ``apps/serve --abtest on`` reports the recipes.

The harness-level fault cases of the cell are
``benchmark/tests/test_hash2e18_grid4.py``.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from benchmark import gen
from benchmark import spans as span_files
from benchmark.reference import grid_linear_sgd as ref
from test_tenant_deployment import (
    F_TEXT,
    MODEL,
    _generator,
    _run_app,
    _stream,
    _weights,
)
from twtml_tpu.config import ConfArguments
from twtml_tpu.telemetry import metrics as _metrics
from twtml_tpu.telemetry import tenants as _tenants_tel

STEPS = [0.005, 0.005, 0.0025, 0.0025]
L2S = [0.1, 0.01, 0.1, 0.01]
GRID = ["--tenants", "4", "--tenantKey", "all",
        "--tenantStepSize", ",".join(map(str, STEPS)),
        "--tenantL2Reg", ",".join(map(str, L2S))]
GRID_MODEL = dict(MODEL, tenants=4, tenantStepSize=STEPS, tenantL2Reg=L2S)


@pytest.fixture(autouse=True)
def _fresh_registries():
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()


def _batches(rows: int, batches: int, seed: int):
    """The mix's generator at a tiny size, featurized as the trainer's
    ragged wire carries it."""
    from twtml_tpu.features.featurizer import Featurizer, Status

    g = _generator(rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                           rows * batches)
    feat = Featurizer(now_ms=g["now_ms"])
    statuses = [Status.from_json(json.loads(line)) for line in chunk.lines]
    return [
        feat.featurize_batch_ragged(
            statuses[b * rows:(b + 1) * rows], row_bucket=rows,
            pre_filtered=True)
        for b in range(batches)
    ]


# ---------------------------------------------------------------------------
# (a) the arm law

def _assert_the_arm_is_the_single_model(arm, alone, f_text, gram, where):
    """The arm law on one arm's ``[F+4]`` weights. Outside the Gram basis:
    bit for bit. In it (PR 50: ``u`` for all arms out of the count build and
    ONE write-back pass, no longer the single model's expression inside a
    loop) float32 rounding, stated as what the CPU backend gives: the
    ``F`` text weights bit for bit — each arm's reduction is the single
    model's own, a sibling of the others' — and the four numeric weights
    within 2 ulp: XLA's CPU backend merges the arms' four
    ``numericᵀ·α_m`` matvecs into one product, which sums in another order
    (1 ulp seen). Far inside the ≤ 2e-6 absolute PARITY.md allows."""
    arm = np.asarray(arm, np.float32)
    alone = np.asarray(alone, np.float32)
    if not gram:
        assert arm.tobytes() == alone.tobytes(), where
        return
    assert arm[:f_text].tobytes() == alone[:f_text].tobytes(), where
    gap = np.abs(arm[f_text:] - alone[f_text:])
    assert (gap <= 2 * np.spacing(np.abs(alone[f_text:]))).all(), (where, gap)
    assert gap.max() <= 2e-6, (where, gap)


@pytest.mark.parametrize("path, kw", [
    ("gram", dict(num_text_features=1 << 14, use_sparse=True, use_gram=True)),
    ("scatter", dict(num_text_features=1 << 14, use_sparse=True,
                     use_gram=False)),
    ("dense", dict(num_text_features=F_TEXT)),
])
def test_each_arm_is_bitwise_the_single_model_under_its_recipe(path, kw):
    """Weights AND every leaf of the fetched output, after three batches,
    the packed wire as the app ships it. ``scatter`` and ``dense`` map the
    whole loop over the arms and stay bit for bit. The Gram case is the
    cell's path — one count matrix and one G a batch, ``C·[w_1…w_M]`` and
    ``Cᵀ·[α_1…α_M]`` each ONE pass, the dual loop mapped — and holds the
    law to float32 rounding (``_assert_the_arm_is_the_single_model``); every
    leaf of the OUTPUT is still bit for bit here, the 1-ulp steps of a
    numeric weight being far under what a margin of this size resolves."""
    import jax

    from twtml_tpu.features.batch import pack_batch
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.parallel import TenantStackModel

    stack = TenantStackModel(4, tenant_key="all", step_sizes=STEPS,
                             l2_regs=L2S, quality=True, **kw)
    singles = [
        StreamingLinearRegressionWithSGD(step_size=s, l2_reg=r, quality=True,
                                         **kw)
        for s, r in zip(STEPS, L2S)
    ]
    for rb in _batches(64, 3, 11):
        wire = stack.pack_for_wire(rb)
        assert wire.buffer.tobytes() == pack_batch(rb).buffer.tobytes()
        out = jax.device_get(stack.step(wire))
        for m, single in enumerate(singles):
            alone = jax.device_get(single.step(pack_batch(rb)))
            for name in alone._fields:
                if getattr(alone, name) is None:
                    assert getattr(out, name) is None   # e.g. ``primal``
                    continue
                got = np.asarray(getattr(out, name))[m]
                assert got.tobytes() == np.asarray(
                    getattr(alone, name)).tobytes(), (path, m, name)
    w = stack.latest_weights
    f_text = kw["num_text_features"]
    assert w.shape == (4, f_text + 4)
    for m, single in enumerate(singles):
        _assert_the_arm_is_the_single_model(
            w[m], single.latest_weights, f_text, path == "gram", (path, m))
    # the recipes really differ: four different models came out
    assert len({w[m].tobytes() for m in range(4)}) == 4


def test_the_gram_program_builds_one_count_matrix_and_one_g_for_all_arms():
    """On the compiled program's op names at 2^18 dims (what a profile
    shows; nothing runs), as ``benchmark/stage_times`` reads them (first
    scope name on a path). In each plane's branch the count build, the
    ``gram_matmul`` product and — since PR 50 — the two contractions with C
    (``predict``: u for all arms; ``writeback``: ONE pass for all arms) are
    OUTSIDE ``arm_map``; under it the map's ``while`` holds ``dual_loop``
    and no ``predict``, ``writeback`` or ``gram_*``. NO op-name in the
    whole program holds both ``arm_map`` and ``predict`` / ``writeback``:
    ``benchmark/layer_metrics/arm_contraction_hbm_share.py`` counts two
    reads of C an ARM against the time under exactly those, and must find
    nothing."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmark import stage_times
    from test_step_scopes import _wire
    from twtml_tpu.parallel import TenantStackModel

    f = 1 << 18
    model = TenantStackModel(4, num_text_features=f, tenant_key="all",
                             step_sizes=STEPS, l2_regs=L2S, quality=True)
    hlo = jax.jit(model._shared, donate_argnums=0).lower(
        jnp.zeros((4, f + 4), jnp.float32), model._hyper,
        _wire("packed", 8, 16)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert not any("/tenant_map/" in n for n in names)

    def stages(ns):
        return {stage_times.stage_of(n) for n in ns}

    for branch in (0, 1, 2):
        inside = [n for n in names if f"/cond/branch_{branch}_fun/" in n
                  and not n.startswith("jit(shared)/gram_count/")]
        once = [n for n in inside if "/arm_map/" not in n]
        assert stages(once) == {
            "gram_count", "gram_matmul", "predict", "writeback"}, branch
        mapped = [n for n in inside if "/arm_map/while/body/" in n]
        # ``other``: the map's own slicing of its operands
        assert stages(mapped) == {"dual_loop", "other"}, branch
    both = [n for n in names if "arm_map" in n.split("/")
            and {"predict", "writeback"} & set(n.split("/"))]
    assert not both, both
    # after the switch: the per-arm stats are mapped under ``predict`` WITHOUT
    # the arm scope, the quality vector under it, and nothing else is there
    after = [n for n in names if n.startswith("jit(shared)/arm_map/")]
    assert stages(n for n in after if "/while/body/" in n) == {
        "quality", "other"}
    assert any(n.startswith("jit(shared)/predict/while/body/") for n in names)


def test_through_the_app_an_arm_is_the_single_models_run(
        tmp_path, monkeypatch):
    """The normal path (block ingest, ragged wire, FetchPipeline,
    checkpoint) at 2^16 dims, where the step takes the Gram basis: the
    champion's and the last challenger's rows of the ``[4, F+4]``
    checkpoint against two single-model runs of the same command line under
    those recipes, to the Gram basis's float32 rounding (the text weights
    bit for bit, the four numeric ones within 2 ulp); and the printed lines
    are the champion's, character for character."""
    rows, batches = 64, 3
    _g, _chunk, path = _stream(tmp_path, rows, batches, 7)
    wide = ["--numTextFeatures", "65536"]
    _t, printed = _run_app(monkeypatch, path, str(tmp_path / "grid"), rows,
                           batches, GRID + wide)
    w = _weights(str(tmp_path / "grid"))
    assert w.shape == (4, 65536 + 4)
    for m in (0, 3):
        _t, alone = _run_app(
            monkeypatch, path, str(tmp_path / f"arm{m}"), rows, batches,
            wide + ["--stepSize", str(STEPS[m]), "--l2Reg", str(L2S[m])])
        _assert_the_arm_is_the_single_model(
            w[m], _weights(str(tmp_path / f"arm{m}")), 65536, True, m)
        if m == 0:
            assert printed == alone
    assert [p["batch"] for p in printed] == [rows] * batches   # B, not 4·B
    assert printed[-1]["count"] == rows * batches


# ---------------------------------------------------------------------------
# (b) the plain reference and its control

def _dev(w, r) -> float:
    return float(np.abs(w - r).sum() / np.abs(r).sum())


def test_reference_against_the_program_and_the_control_is_not_it(
        tmp_path, monkeypatch):
    rows, batches = 64, 4
    g, chunk, path = _stream(tmp_path, rows, batches, 7)
    ckpt = str(tmp_path / "ck")
    totals, printed = _run_app(monkeypatch, path, ckpt, rows, batches, GRID)
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches, model=GRID_MODEL,
        generator=g)
    assert totals["batches"] == batches and totals["tenants"] == 4
    assert [p["batch"] for p in printed] == [s["count"] for s in stats] == (
        [rows] * batches)
    for p, s in zip(printed, stats):
        # each side rounds every prediction HALF_UP before it squares: at
        # 64 rows ONE prediction that float32 rounds the other way moves
        # the batch's mse by 2·|error| ÷ 64, ~10 units of ~98,000 (at the
        # cell's 2,048 rows a third of a unit); the champion's line all the
        # same, not a challenger's, which sits ~0.5% away
        assert abs(p["mse"] - s["mse"]) <= 2e-4 * s["mse"]
        assert all(abs(p["mse"] - other) > abs(p["mse"] - s["mse"])
                   for other in s["arm_mse"][1:] if other != s["mse"])
    assert len(set(stats[-1]["arm_mse"])) == 4
    w = _weights(ckpt)
    assert w.shape == learner.w.shape == (4, F_TEXT + 4)
    assert _dev(w, learner.w) < 5e-6           # float32 against float64
    # the limit also sees recipes that were swapped or all made arm 0's
    assert _dev(w[[0, 2, 1, 3]], learner.w) > 1e-3
    assert _dev(np.broadcast_to(w[0], w.shape), learner.w) > 1e-3
    control, _stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches, model=GRID_MODEL,
        generator=g, precision="bf16")
    assert _dev(control.w, learner.w) > 5e-6


def test_the_reference_refuses_lists_of_another_length():
    with pytest.raises(ValueError, match="4 arms"):
        ref.recipes(dict(GRID_MODEL, tenantL2Reg=[0.1, 0.01]))


# ---------------------------------------------------------------------------
# (c) the batch's line

def test_the_line_is_arm_0s_and_a_nan_in_arm_3_alone_reaches_the_sentinel():
    import jax

    from twtml_tpu.apps.common import DivergenceSentinel
    from twtml_tpu.parallel import TenantStackModel
    from twtml_tpu.parallel.tenants import aggregate_tenant_output

    model = TenantStackModel(4, num_text_features=F_TEXT, tenant_key="all",
                             step_sizes=STEPS, l2_regs=L2S)
    first, second = _batches(32, 2, 3)
    model.step(first)
    out = jax.device_get(model.step(second))
    line = aggregate_tenant_output(out, second, model)
    assert float(line.count) == 32.0 == float(out.count[0])
    for name in ("mse", "real_stdev", "pred_stdev"):
        assert np.float32(getattr(line, name)) == getattr(out, name)[0]
    assert np.array_equal(line.predictions, out.predictions[0])
    assert line.predictions.shape == (32,)
    assert DivergenceSentinel._finite(line)
    # arm 3's weights poisoned: its stats go NaN, arms 0 to 2 stay sound
    w = model.latest_weights.copy()
    w[3, 0] = np.nan
    model.set_initial_weights(w)
    out = jax.device_get(model.step(second))
    assert np.isfinite(np.asarray(out.mse)[:3]).all()
    assert not np.isfinite(np.asarray(out.mse)[3])
    line = aggregate_tenant_output(out, second, model)
    assert not DivergenceSentinel._finite(line)
    assert float(line.count) == 32.0


# ---------------------------------------------------------------------------
# (d) the wire, the fetch and the trace

def test_one_upload_of_the_single_models_bytes_one_fetch_no_split(
        tmp_path, monkeypatch):
    import jax

    rows, batches = 64, 3
    _g, _chunk, path = _stream(tmp_path, rows, batches, 5)
    events, fetches = {}, {}
    real = jax.device_get
    for name, extra in (("grid", GRID), ("single", [])):
        trace = str(tmp_path / f"{name}.json")
        calls = []
        monkeypatch.setattr(
            jax, "device_get", lambda x, c=calls: (c.append(1), real(x))[1])
        _run_app(monkeypatch, path, str(tmp_path / name), rows, batches,
                 extra + ["--trace", trace])
        monkeypatch.setattr(jax, "device_get", real)
        events[name], fetches[name] = span_files.load_events(trace), len(calls)

    def by(name, kind):
        return [e for e in events[name] if e.get("name") == kind]

    assert fetches["grid"] == fetches["single"]        # one a batch
    packs = by("grid", "wire_pack")
    assert len(packs) == len(by("grid", "dispatch")) == batches
    assert [e["args"]["wire_bytes"] for e in packs] == [
        e["args"]["wire_bytes"] for e in by("single", "wire_pack")]
    assert not by("grid", "tenant_split")
    routed = by("grid", "tenant_rows")
    assert len(routed) == batches
    for e in routed:
        a = e["args"]
        assert a["key"] == "all" and a["rows"] == [rows] * 4
        assert a["bucket"] == rows and a["pad_rows"] == 0
    reg = _metrics.get_registry()
    assert reg.counter("tenants.shared_batches").snapshot() == batches
    view = _tenants_tel.last_tenants()
    assert [(t["stepSize"], t["l2Reg"]) for t in view["tenants"]] == list(
        zip(STEPS, L2S))
    assert [t["batch"] for t in view["tenants"]] == [rows] * 4


def test_a_partitioned_run_says_its_key_and_counts_no_shared_batch(
        tmp_path, monkeypatch):
    rows, batches = 64, 2
    _g, _chunk, path = _stream(tmp_path, rows, batches, 5)
    trace = str(tmp_path / "spans.json")
    _run_app(monkeypatch, path, str(tmp_path / "ck"), rows, batches,
             ["--tenants", "4", "--trace", trace])
    routed = [e for e in span_files.load_events(trace)
              if e.get("name") == "tenant_rows"]
    assert [e["args"]["key"] for e in routed] == ["hash"] * batches
    assert _metrics.get_registry().counter(
        "tenants.shared_batches").snapshot() == 0
    view = _tenants_tel.last_tenants()   # one recipe, said four times
    assert [(t["stepSize"], t["l2Reg"]) for t in view["tenants"]] == [
        (0.005, 0.1)] * 4


# ---------------------------------------------------------------------------
# (e) refusals

@pytest.mark.parametrize("flags, said", [
    (["--tenants", "4", "--tenantStepSize", "0.1,0.2"],
     "--tenantStepSize names 2 tenant"),
    (["--tenantL2Reg", "0.1,0.01", "--tenants", "3"],
     "--tenantL2Reg names 2 tenant"),
    (["--tenants", "2", "--tenantL2Reg", "0.1,much"], "comma-separated"),
    (["--tenantStepSize", "0.1,0.2"], "--tenants is 1"),
])
def test_lists_of_another_length_are_refused_at_the_command_line(flags, said):
    with pytest.raises(SystemExit) as exc:
        ConfArguments().parse(flags)
    assert said in str(exc.value)


def test_the_parsed_lists_reach_the_models_hyper_parameters():
    from twtml_tpu.parallel import TenantStackModel

    conf = ConfArguments().parse(GRID + ["--stepSize", "0.7"])
    assert conf.tenant_recipes() == (STEPS, L2S)
    model = TenantStackModel.from_conf(conf)
    assert model.shared_rows and model.tenant_key == "all"
    assert np.asarray(model._hyper["step_size"]).tolist() == (
        np.asarray(STEPS, np.float32).tolist())
    assert np.asarray(model._hyper["l2_reg"]).tolist() == (
        np.asarray(L2S, np.float32).tolist())
    # a list left out falls back to the run's one value, for every tenant
    conf = ConfArguments().parse(
        ["--tenants", "3", "--stepSize", "0.7", "--tenantL2Reg", "1,2,3"])
    assert conf.tenant_recipes() == ([0.7] * 3, [1.0, 2.0, 3.0])


@pytest.mark.parametrize("kw, said", [
    (dict(wire_pack="group"), "--wirePack group"),
    (dict(mesh="a mesh"), "local[1]"),
])
def test_the_plane_refuses_what_all_has_no_form_of(kw, said):
    from twtml_tpu.parallel import TenantStackModel

    with pytest.raises(ValueError, match="--tenantKey all") as exc:
        TenantStackModel(4, num_text_features=F_TEXT, tenant_key="all", **kw)
    assert said in str(exc.value)


def test_the_step_builder_refuses_arms_under_a_data_axis():
    from twtml_tpu.models.sgd import make_sgd_train_step

    with pytest.raises(ValueError, match="one device"):
        make_sgd_train_step(num_text_features=F_TEXT, num_iterations=50,
                            step_size=0.1, arms=True, axis_name="data")


@pytest.mark.parametrize("extra, said", [
    (["--master", "local[4]"], "one device"),
    (["--master", "local[1]", "--wirePack", "group"], "--wirePack group"),
])
def test_the_app_refuses_a_mesh_and_the_coalesced_wire(extra, said):
    from twtml_tpu.apps.common import build_model

    conf = ConfArguments().parse(
        ["--backend", "cpu", "--numTextFeatures", str(F_TEXT)] + GRID + extra)
    with pytest.raises(SystemExit) as exc:
        build_model(conf)
    assert said in str(exc.value)


def test_all_is_not_a_routing_key():
    from twtml_tpu.features.batch import tenant_route_keys

    (rb,) = _batches(8, 1, 3)
    with pytest.raises(ValueError, match="'hash' or 'lang'"):
        tenant_route_keys(rb, 4, "all")


# ---------------------------------------------------------------------------
# (f) the checkpoint's stamp, the resume, the serving plane

def test_the_stamp_a_resume_under_other_lists_and_serving_under_abtest(
        tmp_path, monkeypatch):
    from twtml_tpu.apps import serve as serve_app
    from twtml_tpu.checkpoint import Checkpointer
    from twtml_tpu.serving.client import ServingClient

    rows, batches = 64, 3
    g, chunk, path = _stream(tmp_path, rows, batches, 7)
    ckpt = str(tmp_path / "ck")
    _run_app(monkeypatch, path, ckpt, rows, batches, GRID)
    stamp = Checkpointer(ckpt).latest_meta()["tenants"]
    assert stamp == {"count": 4, "key": "all", "stepSize": STEPS,
                     "l2Reg": L2S}
    # the same lists resume; other lists, another key, no lists: refused
    # before anything trains (the stack's rows would change their meaning)
    for other in (
        GRID[:5] + ["0.005,0.005,0.005,0.005"] + GRID[6:],
        ["--tenants", "4", "--tenantKey", "hash"],
        ["--tenants", "4", "--tenantKey", "all"],
    ):
        with pytest.raises(SystemExit, match="per-tenant lists"):
            _run_app(monkeypatch, path, ckpt, rows, batches, other)
    totals, _printed = _run_app(monkeypatch, path, ckpt, rows, batches + 1,
                                GRID)   # resumes: the file holds no 4th batch
    assert (totals["batches"], totals["count"]) == (batches, rows * batches)

    def conf(*more):
        return ConfArguments().parse([
            "--backend", "cpu", "--master", "local[1]", "--checkpointDir",
            ckpt, "--numTextFeatures", str(F_TEXT), "--servePort", "0",
            "--serveBatchRows", "32", "--serveMaxWaitMs", "2",
            "--servePromoteEvery", "600", *more])

    # without --abtest a row would have to BELONG to one arm: refused
    with pytest.raises(SystemExit, match="--abtest on"):
        serve_app.run(conf())
    stop, up, ready, result = threading.Event(), threading.Event(), {}, {}

    def started(server, plane, promoter):
        ready["port"] = server._runner.addresses[0][1]
        up.set()

    thread = threading.Thread(target=lambda: result.update(
        stats=serve_app.run(conf("--abtest", "on"), started=started,
                            stop_event=stop)))
    thread.start()
    try:
        assert up.wait(timeout=300), "serve app never came up"
        res = ServingClient(f"http://127.0.0.1:{ready['port']}").predict(
            gen.predict_rows(chunk, list(range(8))))
    finally:
        stop.set()
        thread.join(timeout=120)
    assert not thread.is_alive() and res["servedRows"] == 8
    view = result["stats"]
    assert view["tenantKey"] == "all" and view["champion"] in range(4)
    assert [(s["tenant"], s["stepSize"], s["l2Reg"])
            for s in view["shadows"]] == list(zip(range(4), STEPS, L2S))
