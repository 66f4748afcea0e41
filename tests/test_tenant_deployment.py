"""The tenant plane as a deployment (PR 35, configuration ``hash2e18-ab4``):
the benchmark's plain reference of it (``benchmark/reference/
tenant_linear_sgd.py``: NumPy, float64, its own copy of the routing rule)
against the program's plane through ``apps.linear_regression.run`` — block
ingest, ragged wire, ``FetchPipeline``, verified ``[M, F+4]`` checkpoint —
at sizes a CPU holds; the routing rule's two copies; the parity law; and the
spans the plane leaves under ``--trace``.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re

import numpy as np
import pytest

from benchmark import gen, manifest
from benchmark import spans as span_files
from benchmark.reference import linear_sgd
from benchmark.reference import tenant_linear_sgd as ref
from twtml_tpu.config import ConfArguments
from twtml_tpu.features.batch import (
    RaggedUnitBatch,
    split_batch_tenants,
    tenant_route_keys,
)
from twtml_tpu.telemetry import metrics as _metrics
from twtml_tpu.telemetry import tenants as _tenants_tel

CLOSED = "http://127.0.0.1:9"
F_TEXT = 4096
MODEL = {"numTextFeatures": F_TEXT, "numIterations": 50, "stepSize": 0.005,
         "l2Reg": 0.1}


@pytest.fixture(autouse=True)
def _fresh_registries():
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()


def _generator(rows: int, batches: int) -> dict:
    g = dict(manifest.load_json(
        manifest.traffic_path("trimmed-kept-280"))["generator"])
    g.update(pool_lines=rows * batches, length_block=rows, vocab_size=2000)
    return g


def _stream(tmp_path, rows, batches, seed):
    """The mix's generator at a tiny size: its truth columns (one chunk) and
    its lines as a replay file."""
    g = _generator(rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                           rows * batches)
    path = tmp_path / f"stream{seed}.jsonl"
    path.write_text("".join(line + "\n" for line in chunk.lines),
                    encoding="utf-8")
    return g, chunk, str(path)


def _run_app(monkeypatch, path, ckpt, rows, batches, extra):
    from twtml_tpu.apps import linear_regression as app

    monkeypatch.setenv("TWTML_NOW_MS", "1785320000000")
    conf = ConfArguments().parse([
        "--source", "replay", "--replayFile", path, "--ingest", "block",
        "--seconds", "0", "--backend", "cpu", "--master", "local[1]",
        "--batchBucket", str(rows), "--numTextFeatures", str(F_TEXT),
        "--l2Reg", "0.1", "--lightning", CLOSED, "--twtweb", CLOSED,
        "--webTimeout", "0.2", "--checkpointDir", ckpt, *extra,
    ])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        totals = app.run(conf, max_batches=batches)
    lines = [ln.split() for ln in out.getvalue().splitlines()
             if ln.startswith("count: ")]
    return totals, [{"count": int(f[1]), "batch": int(f[3]),
                     "mse": float(f[5])} for f in lines]


def _weights(ckpt):
    from twtml_tpu.serving import load_servable

    snapshot, reason = load_servable(ckpt)
    assert snapshot is not None, reason
    return np.asarray(snapshot.weights, np.float64)


def _dry_seed(rows, batches, tenants) -> int:
    """The first seed whose stream leaves some tenant without a row in a
    batch AFTER the first (its weights are not zero then, so an L2 shrink
    would show), by the reference's own routing."""
    for seed in range(1, 200):
        g = _generator(rows, batches)
        chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                               rows * batches)
        ids = np.array([ref.route(t, tenants) for t in chunk.text])
        per_batch = [np.bincount(ids[b * rows:(b + 1) * rows],
                                 minlength=tenants) for b in range(batches)]
        if any(0 in c for c in per_batch[1:]) and all(
                c.sum() == rows for c in per_batch):
            return seed
    raise AssertionError("no seed leaves a tenant dry")


@pytest.mark.parametrize("tenants,rows,batches,seed", [
    (4, 64, 4, 7),
    (3, 8, 6, None),    # a tenant left dry in one batch
])
def test_reference_against_the_plane_through_the_app(
        tmp_path, monkeypatch, tenants, rows, batches, seed):
    dry = seed is None
    seed = _dry_seed(rows, batches, tenants) if dry else seed
    g, chunk, path = _stream(tmp_path, rows, batches, seed)
    ckpt = str(tmp_path / "ck")
    totals, printed = _run_app(monkeypatch, path, ckpt, rows, batches,
                               ["--tenants", str(tenants)])
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches,
        model=dict(MODEL, tenants=tenants), generator=g)
    assert totals["batches"] == batches and totals["tenants"] == tenants
    assert [p["batch"] for p in printed] == [s["count"] for s in stats]
    assert all(sum(s["tenant_rows"]) == rows for s in stats)
    if dry:
        assert any(0 in s["tenant_rows"] for s in stats[1:])
    for p, s in zip(printed, stats):   # both sides print a HALF_UP integer
        assert abs(p["mse"] - s["mse"]) <= 1.0
    w = _weights(ckpt)
    assert w.shape == learner.w.shape == (tenants, F_TEXT + 4)
    # float32 against float64: ~2e-7. A dry tenant's L2 shrink alone (0.7%
    # of its weights) would read ~2e-3; rows routed otherwise, far more
    assert np.abs(w - learner.w).sum() / np.abs(learner.w).sum() < 5e-6


def test_a_program_that_routes_every_row_to_tenant_0_is_seen(
        tmp_path, monkeypatch):
    """The fault ``weights_dev`` over the whole ``[M, F+4]`` array is there
    for (``benchmark/tests/test_hash2e18_ab4.py`` drives it through the
    harness): counts and batch sizes stay right, the weights do not."""
    from twtml_tpu.parallel import tenants

    monkeypatch.setattr(
        tenants, "tenant_route_keys",
        lambda batch, m, mode="hash": np.zeros(batch.mask.shape[0], np.int32))
    g, chunk, path = _stream(tmp_path, 64, 3, 7)
    ckpt = str(tmp_path / "ck")
    _totals, printed = _run_app(monkeypatch, path, ckpt, 64, 3,
                                ["--tenants", "4"])
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=64, n_batches=3, model=dict(MODEL, tenants=4),
        generator=g)
    assert [p["batch"] for p in printed] == [s["count"] for s in stats]
    w = _weights(ckpt)
    assert not w[1:].any() and w[0].any()
    assert np.abs(w - learner.w).sum() / np.abs(learner.w).sum() > 0.5


def test_a_dry_tenant_keeps_its_weights_in_program_and_reference():
    """No gradient step and NO L2 shrink for a tenant without a row: the
    program's step on an all-padding batch leaves non-zero weights as they
    are, bit for bit, and the reference's learner is not touched."""
    from twtml_tpu.parallel import TenantStackModel

    rng = np.random.default_rng(5)
    texts = ["tweet number %d with words" % i for i in range(16)]
    units = np.frombuffer("".join(texts).encode("utf-16-le"), np.uint16)
    offsets = np.zeros(17, np.int32)
    np.cumsum([len(t) for t in texts], out=offsets[1:])
    rb = RaggedUnitBatch(
        units, offsets, rng.random((16, 4)).astype(np.float32),
        rng.random(16).astype(np.float32) * 100, np.ones(16, np.float32),
        row_len=32,
    )
    model = TenantStackModel(3, num_text_features=F_TEXT, l2_reg=0.1,
                             step_size=0.005)
    start = rng.standard_normal((3, F_TEXT + 4)).astype(np.float32)
    model.set_initial_weights(start)
    ids = np.where(np.arange(16) % 2 == 0, 0, 2).astype(np.int32)  # 1 is dry
    out = model.step(model.prepare_wire_from_parts(
        split_batch_tenants(rb, ids, 3)))
    assert np.asarray(out.count).tolist() == [8.0, 0.0, 8.0]
    w = model.latest_weights
    assert w[1].tobytes() == start[1].tobytes()
    assert not np.array_equal(w[0], start[0])

    learner = ref.TenantLinearSGD(3, F_TEXT, l2_reg=0.1, step_size=0.005)
    for t, w0 in zip(learner.tenants, start):
        t.w = w0.astype(np.float64)
    route = ref.route
    try:   # the same split, through the reference's own step
        ref.route = lambda text, m, _ids=dict(zip(texts, ids)): int(_ids[text])
        stats = learner.step_batch(
            texts, *(np.ones(16) for _ in range(4)), np.arange(16.0),
            now_ms=2.0)
    finally:
        ref.route = route
    assert stats["tenant_rows"] == [8, 0, 8]
    assert np.array_equal(learner.w[1], start[1].astype(np.float64))


def _random_texts(n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    pools = (
        "abcdefghijklmnopqrstuvwxyz  ABCDEFGHIJ#@:/.'",          # ASCII
        "abc défg ÉÀÖ ñç İ ß 中文字 かな",                          # BMP
        "ab \U0001f600\U0001f61e c",                               # surrogates
    )
    out = []
    for i in range(n):
        pool = pools[0] if i % 10 < 7 else pools[1 + i % 2]
        out.append("".join(rng.choice(list(pool), rng.integers(1, 281))))
    return out


@pytest.mark.parametrize("tenants", [2, 3, 4, 8])
def test_routing_rules_two_copies_agree_on_1e4_rows(tenants):
    """``features/batch.tenant_route_keys`` over the units the ragged wire
    carries (an all-ASCII text as it is, any other lower-cased) against the
    reference's rule written out in Python's own integers."""
    texts = _random_texts(10_000, 11)
    wire = [ref.wire_text(t) for t in texts]
    units = [np.frombuffer(t.encode("utf-16-le", "surrogatepass"), "<u2")
             for t in wire]
    offsets = np.zeros(len(texts) + 1, np.int32)
    np.cumsum([u.size for u in units], out=offsets[1:])
    rb = RaggedUnitBatch(
        np.concatenate(units).astype(np.uint16), offsets,
        np.zeros((len(texts), 4), np.float32),
        np.zeros(len(texts), np.float32), np.ones(len(texts), np.float32),
        row_len=512,
    )
    got = tenant_route_keys(rb, tenants, "hash")
    want = np.array([ref.route(t, tenants) for t in texts])
    assert np.array_equal(got, want)
    assert np.bincount(want, minlength=tenants).min() > 0.8 * 10_000 / tenants


def test_the_key_reads_the_wires_units_not_the_lowered_text():
    """The finding PR 35 states (reference's docstring, PERF.md section 6):
    an all-ASCII row ships with its case kept, so its key reads its
    capitals; a key over the lower-cased text would route it elsewhere."""
    assert ref.wire_text("Good Morning") == "Good Morning"
    assert ref.wire_text("Café É") == "café é"
    texts = ["Word%d Another" % i for i in range(64)]
    assert any(ref.route(t, 4) != ref.route(t.lower(), 4) for t in texts)


@pytest.mark.parametrize("tenants,wire_pack", [
    (3, "stacked"), (4, "stacked"), (4, "group"),
])
def test_parity_law(tmp_path, tenants, wire_pack):
    """Routing moves rows, never semantics: over the generator's stream the
    tenants' valid rows add up to the batch's, and each tenant's share of
    the stacked weights is, bit for bit, a single model's fed that tenant's
    routed rows."""
    from twtml_tpu.features.featurizer import Featurizer, Status
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.parallel import TenantStackModel

    rows, batches = 32, 3
    g = _generator(rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, 3), 3, 0, rows * batches)
    feat = Featurizer(now_ms=g["now_ms"])
    statuses = [Status.from_json(json.loads(line)) for line in chunk.lines]
    kw = dict(num_text_features=F_TEXT, l2_reg=0.1, step_size=0.005)
    stack = TenantStackModel(tenants, wire_pack=wire_pack, **kw)
    singles = [StreamingLinearRegressionWithSGD(**kw) for _ in range(tenants)]
    for b in range(batches):
        rb = feat.featurize_batch_ragged(
            statuses[b * rows:(b + 1) * rows], row_bucket=rows,
            pre_filtered=True)
        parts = stack.split(rb)
        out = stack.step(rb)
        counts = np.asarray(out.count)
        assert counts.sum() == rows
        assert counts.tolist() == [p.num_valid for p in parts]
        for single, part in zip(singles, parts):
            if part.num_valid:
                single.step(part)
    for i, single in enumerate(singles):
        assert single.latest_weights.tobytes() == (
            stack.latest_weights[i].tobytes())


@pytest.mark.parametrize("rows,bucket", [
    (32, 32),      # a batch under one rung: the top rung, its own rows
    (256, 128),    # 256 rows over 4 tenants: the first rung
])
def test_the_planes_spans_under_trace_and_none_without_it(
        tmp_path, monkeypatch, rows, bucket):
    batches = 3
    _g, _chunk, path = _stream(tmp_path, rows, batches, 5)
    spans = {}
    for tenants in (4, 1):
        trace = str(tmp_path / f"spans{tenants}.json")
        _run_app(monkeypatch, path, str(tmp_path / f"ck{tenants}"), rows,
                 batches, ["--tenants", str(tenants), "--trace", trace])
        spans[tenants] = span_files.load_events(trace)
    by = lambda evs, name: [e for e in evs if e.get("name") == name]  # noqa: E731
    split = by(spans[4], "tenant_split")
    assert len(split) == batches and all(e["ph"] == "X" for e in split)
    for e in split:
        a = e["args"]
        assert a["rows"] == rows and a["tenants"] == 4 and a["bytes"] > 0
        assert isinstance(a["batch"], int)
    packs = by(spans[4], "wire_pack")   # the split runs inside wire_pack
    assert [e["args"]["wire_bytes"] for e in packs] == [
        e["args"]["bytes"] for e in split]
    routed = by(spans[4], "tenant_rows")
    assert len(routed) == batches
    for e in routed:
        a = e["args"]
        assert e["ph"] == "i" and len(a["rows"]) == 4
        assert sum(a["rows"]) == rows and max(a["rows"]) <= bucket
        # the rung the split took for this batch, and what of M·rung rows
        # the step worked on was padding
        assert a["bucket"] == bucket
        assert a["pad_rows"] == 4 * a["bucket"] - sum(a["rows"])
    # the plane's worst tenant, one instant a delivered batch
    planes = by(spans[4], "gram_plane")
    assert len(planes) == batches
    assert all(isinstance(e["args"]["plane"], int) for e in planes)
    # the single-model plane never enters parallel/tenants.py
    assert not by(spans[1], "tenant_split") and not by(spans[1], "tenant_rows")
    assert len(by(spans[1], "gram_plane")) == batches


def test_the_mapped_program_carries_tenant_map_around_the_stage_scopes():
    """On the compiled program's op names (what a profile shows): the body
    of the map sits under ``tenant_map`` and the step's stage scopes are
    inside it, so ``benchmark/stage_times`` (first scope name on a path)
    reads the stages as in a single model's program."""
    import jax
    import jax.numpy as jnp

    from benchmark import stage_times
    from twtml_tpu.models.sgd import STAGE_SCOPES
    from twtml_tpu.parallel import TenantStackModel

    m, rows, f_text = 4, 8, 1 << 18
    model = TenantStackModel(m, num_text_features=f_text, l2_reg=0.1,
                             step_size=0.005, quality=True)
    lens = np.full(rows, 8, np.int32)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    one = RaggedUnitBatch(
        np.zeros(int(offsets[-1]), np.uint16), offsets,
        np.zeros((rows, 4), np.float32), np.zeros(rows, np.float32),
        np.ones(rows, np.float32), row_len=16,
    )
    wire = model.prepare_wire_from_parts([one] * m)
    hlo = jax.jit(model._mapped, donate_argnums=0).lower(
        jnp.zeros((m, f_text + 4), jnp.float32), model._hyper, wire,
    ).compile().as_text()
    inside = {n for n in re.findall(r'op_name="([^"]*)"', hlo)
              if n.startswith("jit(_mapped)/tenant_map/while/body/")}
    # ``unpack`` belongs to the one-buffer wire; the stacked wire has none
    for scope in (s for s in STAGE_SCOPES if s != "unpack"):
        hits = [n for n in inside if f"/{scope}/" in n]
        assert hits, scope
        assert {stage_times.stage_of(n) for n in hits} == {scope}


def test_the_mapped_program_is_compiled_for_the_rung():
    """The mapped program's row dimension IS the split's output shape: on
    the compiled program the batch operands are ``[M, R, ...]`` with R the
    rung (128 for 256 rows over 4 tenants) and the rung's units buffer, and
    a stream of evenly split batches compiles ONE program per units bucket
    of the whole batch, not one per tenant or per batch."""
    import jax

    from twtml_tpu.features.featurizer import Featurizer, Status
    from twtml_tpu.parallel import TenantStackModel

    m, rows, batches = 4, 256, 6
    g = _generator(rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, 3), 3, 0, rows * batches)
    feat = Featurizer(now_ms=g["now_ms"])
    statuses = [Status.from_json(json.loads(line)) for line in chunk.lines]
    stack = TenantStackModel(m, num_text_features=F_TEXT, l2_reg=0.1,
                             step_size=0.005)
    buckets, wires = set(), set()
    for b in range(batches):
        rb = feat.featurize_batch_ragged(
            statuses[b * rows:(b + 1) * rows], row_bucket=rows,
            pre_filtered=True)
        wire = stack.prepare_wire(rb)
        assert wire.mask.shape == (m, 128)
        assert wire.offsets.shape == (m, 129)
        assert wire.numeric.shape == (m, 128, 4)
        # the parent's units bucket scaled by 128/256, in whole 4,096s
        half = -(-rb.units.shape[0] // 2)
        assert wire.units.shape == (m, half + (-half) % 4096)
        buckets.add((rb.units.shape[0], str(rb.units.dtype)))
        wires.add((wire.units.shape, str(wire.units.dtype)))
        out = stack.step(wire)
        assert np.asarray(out.predictions).shape == (m, 128)
    prog = stack._prog_for(RaggedUnitBatch)
    # (two neighbouring buckets of the batch may scale to one of the rung)
    assert prog._cache_size() == len(wires) <= len(buckets)
    hlo = prog.lower(stack._weights, stack._hyper, wire).compile().as_text()
    entry = hlo[hlo.index("ENTRY"):]
    params = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(", entry)
    for shape in (f"f32[{m},128]", f"f32[{m},128,4]", f"s32[{m},129]",
                  f"u16[{m},{wire.units.shape[1]}]"):
        assert shape in params, (shape, params)
    assert not any(f",{rows}" in p or f",{rows + 1}]" in p for p in params)


def test_work_count_is_a_quarter_of_one_step_and_cannot_pass_the_spent():
    cfg = manifest.load_json(os.path.join(
        manifest.HERE, "configs", "hash2e18-ab4.json"))
    one = manifest.load_module(os.path.join(
        manifest.HERE, "work_counts", "hash2e18.py")).work(cfg, 1, 0.0)
    ab4 = manifest.load_module(
        manifest.work_count_path(cfg)).work(cfg, 1, 0.0)
    assert ab4["flops"] == one["flops"] / 4 and ab4["peak"] == "int8_ops"
    # Σ 2·n_m²·F over any split of B rows is at least the even split's
    b, f = cfg["batch_rows"], cfg["model"]["numTextFeatures"]
    for split in ([512] * 4, [2048, 0, 0, 0], [500, 520, 530, 498]):
        assert sum(2.0 * n * n * f for n in split) >= ab4["flops"]


def test_reference_is_linear_sgd_per_tenant():
    """M = 1 is ``linear_sgd`` itself: same weights, same stats."""
    g = _generator(32, 2)
    chunk = gen.make_chunk(g, gen.build_vocab(g, 9), 9, 0, 64)
    kw = dict(batch_rows=32, n_batches=2, generator=g)
    single, s1 = linear_sgd.train_on_chunks([chunk], model=MODEL, **kw)
    stacked, s2 = ref.train_on_chunks(
        [chunk], model=dict(MODEL, tenants=1), **kw)
    assert np.array_equal(stacked.w[0], single.w)
    assert [s["mse"] for s in s1] == [s["mse"] for s in s2]
