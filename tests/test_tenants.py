"""Multi-tenant model plane (ISSUE 7): M models, one program, one fetch.

The law under test is threefold:
- **M=1 bit-parity**: the tenant-stacked program produces byte-identical
  weights AND stats to the existing single-tenant program, across the
  stacked and coalesced (group) tenant wires and the ragged wire — the
  parity law applied to the new plane;
- **per-tenant parity**: at M>1 every tenant's trajectory bit-equals a
  separate single-tenant model trained on its routed sub-stream (routing
  moves rows, never semantics);
- **one fetch per tick**: a real M=8 app run makes exactly ONE
  ``jax.device_get`` per dispatched batch — the PR 1/5 counting idiom on
  the new plane (one fetch however many tenants is the whole point).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from twtml_tpu.config import ConfArguments  # noqa: E402
from twtml_tpu.features.batch import (  # noqa: E402
    RAGGED_UNIT_MULTIPLE,
    RaggedUnitBatch,
    TwoRungWire,
    split_batch_tenants,
    stack_batches,
    tenant_route_keys,
    tenant_row_rungs,
    tenant_rows,
)
from twtml_tpu.features.featurizer import Featurizer  # noqa: E402
from twtml_tpu.models import (  # noqa: E402
    StreamingLinearRegressionWithSGD,
    StreamingLogisticRegressionWithSGD,
)
from twtml_tpu.parallel import TenantStackModel  # noqa: E402
from twtml_tpu.parallel.tenants import (  # noqa: E402
    aggregate_tenant_output,
    split_tenant_output,
)
from twtml_tpu.streaming.sources import SyntheticSource  # noqa: E402
from twtml_tpu.telemetry import metrics as _metrics  # noqa: E402
from twtml_tpu.telemetry import tenants as _tenants_tel  # noqa: E402

NOW_MS = 1785320000000


@pytest.fixture(autouse=True)
def _fresh_registries():
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()


def _ragged_batches(n=512, b=256, seed=3, unicode_mix=False):
    feat = Featurizer(now_ms=NOW_MS)
    statuses = list(SyntheticSource(total=n, seed=seed).produce())
    if unicode_mix:
        import dataclasses

        for i, s in enumerate(statuses):
            if i % 3 == 0:
                o = s.retweeted_status
                statuses[i] = dataclasses.replace(
                    s,
                    retweeted_status=dataclasses.replace(
                        o, text=o.text + " café 中文"
                    ),
                )
    return [
        feat.featurize_batch_ragged(
            statuses[i : i + b], row_bucket=b, pre_filtered=True
        )
        for i in range(0, n, b)
    ]


def _unit_batches(n=512, b=256, seed=3):
    feat = Featurizer(now_ms=NOW_MS)
    statuses = list(SyntheticSource(total=n, seed=seed).produce())
    return [
        feat.featurize_batch_units(
            statuses[i : i + b], row_bucket=b, pre_filtered=True
        )
        for i in range(0, n, b)
    ]


# ---------------------------------------------------------------------------
# routing


def test_route_keys_deterministic_and_in_range():
    rb = _ragged_batches()[0]
    ids1 = tenant_route_keys(rb, 8)
    ids2 = tenant_route_keys(rb, 8)
    assert np.array_equal(ids1, ids2)
    assert ids1.shape == (rb.mask.shape[0],)
    assert ids1.min() >= 0 and ids1.max() < 8


def _signature(part):
    """What the compiled program's shape depends on, for any batch type."""
    leaves = part if isinstance(part, tuple) else (
        part.units, part.offsets, part.numeric, part.label, part.mask)
    return (
        type(part), getattr(part, "row_len", None),
        tuple((np.asarray(a).shape, np.asarray(a).dtype) for a in leaves),
    )


def test_split_conserves_rows_and_order():
    """Every valid row lands in exactly one tenant, original relative order
    preserved per tenant, and all M parts share ONE signature: the row
    rung's (256 rows over 4 tenants: the first rung, 128), with that
    rung's units buffer and the parent's units dtype and ``row_len`` — the
    row-conservation invariant the CI smoke asserts end-to-end."""
    rb = _ragged_batches()[0]
    ids = tenant_route_keys(rb, 4)
    parts = split_batch_tenants(rb, ids, 4)
    valid = int(np.asarray(rb.mask).sum())
    assert sum(int(np.asarray(p.mask).sum()) for p in parts) == valid
    offs = np.asarray(rb.offsets, np.int64)
    units = np.asarray(rb.units)
    assert tenant_row_rungs(256, 4) == (128, 256)
    assert len({_signature(p) for p in parts}) == 1
    for m, (rows, part) in enumerate(zip(tenant_rows(rb, ids, 4), parts)):
        assert part.mask.shape == part.label.shape == (128,)
        assert part.offsets.shape == (129,)
        assert part.numeric.shape == (128, 4)
        # the parent's 8,192-unit bucket scaled by 128/256
        assert part.units.shape == (rb.units.shape[0] // 2,)
        assert part.units.dtype == rb.units.dtype
        assert part.row_len == rb.row_len
        assert np.all(np.diff(rows) > 0)  # ascending = order preserved
        p_offs = np.asarray(part.offsets, np.int64)
        for j, r in enumerate(rows):
            got = np.asarray(part.units)[p_offs[j] : p_offs[j + 1]]
            want = units[offs[r] : offs[r + 1]]
            assert np.array_equal(got, want), (m, j, r)
            assert float(part.label[j]) == float(rb.label[r])
            assert np.array_equal(part.numeric[j], rb.numeric[r])


@pytest.mark.parametrize("rows,tenants,row_multiple,want", [
    (2048, 4, 1, (640, 1280, 2048)),
    (2048, 8, 1, (384, 768, 1536, 2048)),
    (1024, 4, 1, (384, 768, 1024)),
    (2048, 4, 3, (768, 1536, 2048)),    # a data axis of 3: lcm(128, 3)
    (2048, 4, 8, (640, 1280, 2048)),
    (2048, 1, 1, (2048,)),              # one tenant: the batch itself
    (16, 8, 1, (16,)),                  # a batch under one rung
    (100, 4, 1, (100,)),
])
def test_the_rung_ladder_is_a_function_of_rows_and_tenants(
        rows, tenants, row_multiple, want):
    assert tenant_row_rungs(rows, tenants, row_multiple) == want


def _rung_batch():
    """1,024 rows, the first 300 of 16 units and the rest of 4: 7,696
    units in an 8,192-unit bucket. At M = 4 the rungs are 384 rows (3,072
    units scaled, rounded up to 4,096), 768 (6,144, rounded up to 8,192)
    and 1,024 (the parent's 8,192)."""
    rng = np.random.default_rng(36)
    lens = np.where(np.arange(1024) < 300, 16, 4)
    offsets = np.zeros(1025, np.int32)
    np.cumsum(lens, out=offsets[1:])
    units = np.zeros(8192, np.uint16)
    units[: offsets[-1]] = rng.integers(32, 0x3000, offsets[-1])
    return RaggedUnitBatch(
        units, offsets, rng.random((1024, 4)).astype(np.float32),
        (rng.random(1024) * 100).astype(np.float32),
        np.ones(1024, np.float32), row_len=32,
    )


def _rest_over(ids, tenants):
    """Spread the rows still at -1 evenly over ``tenants``."""
    rest = np.nonzero(ids < 0)[0]
    ids[rest] = np.asarray(tenants)[np.arange(rest.size) % len(tenants)]
    return ids


def _ids_even():
    return (np.arange(1024) % 4).astype(np.int32)


def _ids_one_row_over():
    ids = np.full(1024, -1, np.int32)
    ids[300:685] = 0          # 385 short rows: one over the first rung
    return _rest_over(ids, [1, 2, 3])


def _ids_units_over():
    ids = np.full(1024, -1, np.int32)
    ids[:300] = 0             # 300 rows fit 384; their 4,800 units do not
    return _rest_over(ids, [1, 2, 3])


@pytest.mark.parametrize("tenants,ids,rung,n_units,rest", [
    pytest.param(4, _ids_even, 384, 4096, 384, id="even-first_rung"),
    pytest.param(4, _ids_one_row_over, 768, 8192, 384,
                 id="one_row_over-next"),
    pytest.param(4, _ids_units_over, 768, 8192, 384, id="units_over-next"),
    pytest.param(3, lambda: np.zeros(1024, np.int32), 1024, 8192, 512,
                 id="all_to_tenant_0-top_rung"),
    pytest.param(1, lambda: np.zeros(1024, np.int32), 1024, 8192, 1024,
                 id="one_tenant-pass_through"),
])
def test_split_takes_the_rung_the_batch_calls_for(
        tenants, ids, rung, n_units, rest):
    """Tenant 0 is the fullest in every case: ITS part takes the rung its
    rows and units call for, the others the rung the fullest of THEM needs
    (``rest``: the ladder's first, 384 rows at M = 4 and 512 at M = 3, with
    a 4,096-unit buffer either way, wherever they hold a quarter of the
    batch or nothing). ``one_rung`` pads all to the fullest's, as every
    split did until PR 49."""
    rb = _rung_batch()
    ids = ids()
    parts = split_batch_tenants(rb, ids, tenants)
    assert len(parts) == tenants
    assert len({_signature(p) for p in parts[1:]}) <= 1
    assert [p.mask.shape[0] for p in parts] == [rung] + [rest] * (tenants - 1)
    assert {p.units.shape[0] for p in parts[1:]} <= {4096}
    assert parts[0].mask.shape == (rung,) and parts[0].row_len == rb.row_len
    assert parts[0].offsets.shape == (rung + 1,)
    assert parts[0].units.shape == (n_units,)
    assert n_units % RAGGED_UNIT_MULTIPLE == 0
    one = split_batch_tenants(rb, ids, tenants, one_rung=True)
    assert {_signature(p) for p in one} == {_signature(parts[0])}
    counts = np.bincount(ids, minlength=tenants)
    assert [p.num_valid for p in parts] == counts.tolist()
    for p, n in zip(parts, counts):    # rows to the front, padding behind
        assert np.asarray(p.mask)[:n].all() and not np.asarray(p.mask)[n:].any()
        assert (np.asarray(p.offsets)[n:] == np.asarray(p.offsets)[n]).all()
    if rung == 1024:
        # the top rung is the parent's tenant wire byte for byte: the tenant
        # that got every row gets the batch back, a dry one is all padding
        for f in ("units", "offsets", "numeric", "label", "mask"):
            got, want = getattr(parts[0], f), getattr(rb, f)
            assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
        for p in parts[1:]:
            assert int(np.asarray(p.mask).sum()) == 0
            assert int(np.asarray(p.offsets)[-1]) == 0
            assert not np.asarray(p.units).any()
    # a pinned rung reproduces the parent's shape for any split
    top = split_batch_tenants(rb, ids, tenants, rung=1024)
    assert {_signature(p) for p in top} == {_signature(rb)}
    for p, q in zip(parts, top):
        n = p.num_valid
        assert np.array_equal(p.label[:n], q.label[:n])
        assert np.array_equal(
            p.units[: p.offsets[n]], q.units[: q.offsets[n]])


def test_compile_signature_of_a_stacked_tenant_wire_is_the_rungs():
    from twtml_tpu.features.batch import wire_signature

    rb = _ragged_batches()[0]
    wire = TenantStackModel(4).prepare_wire(rb)
    sig = wire_signature(wire, rb)
    assert (sig["rows"], sig["units_len"]) == (128, rb.units.shape[0] // 2)
    assert sig["row_len"] == rb.row_len and sig["wire"] == "RaggedUnitBatch"
    assert wire_signature(rb, rb)["rows"] == 256


def test_split_rejects_a_pinned_rung_too_small():
    with pytest.raises(ValueError, match="rung"):
        split_batch_tenants(_rung_batch(), _ids_even(), 4, rung=128)


def test_split_padded_wires_take_the_rung_on_their_row_axis():
    """``UnitBatch`` / ``FeatureBatch`` parts: the same rung rule on rows,
    token width unchanged."""
    ub = _unit_batches()[0]
    parts = split_batch_tenants(ub, tenant_route_keys(ub, 4), 4)
    assert len({_signature(p) for p in parts}) == 1
    assert parts[0].units.shape == (128, ub.units.shape[1])
    assert parts[0].units.dtype == ub.units.dtype
    assert parts[0].mask.shape == (128,)
    assert sum(p.num_valid for p in parts) == ub.num_valid
    stacked = stack_batches(parts)
    assert stacked.units.shape == (4, 128, ub.units.shape[1])
    # a data axis of 3 has no rung under 256 rows but the batch itself
    wide = split_batch_tenants(
        ub, tenant_route_keys(ub, 4), 4, row_multiple=3)
    assert wide[0].mask.shape == (256,)


def test_lang_key_separates_scripts():
    rb = _ragged_batches(unicode_mix=True)[0]
    ids = tenant_route_keys(rb, 4, mode="lang")
    valid = np.asarray(rb.mask) > 0
    # the synthetic mix has both pure-ASCII and wide rows → >1 class
    assert len(set(ids[valid].tolist())) > 1


def test_lang_key_rejects_host_hash_wire():
    feat = Featurizer(now_ms=NOW_MS)
    statuses = list(SyntheticSource(total=64, seed=3).produce())
    fb = feat.featurize_batch(statuses, row_bucket=64, pre_filtered=True)
    with pytest.raises(ValueError, match="lang"):
        tenant_route_keys(fb, 4, mode="lang")


# ---------------------------------------------------------------------------
# M=1 bit-parity (acceptance criterion)


@pytest.mark.parametrize("wire_pack", ["stacked", "group"])
def test_m1_bit_parity_ragged(wire_pack):
    """The M=1 tenant-stacked program bit-equals the existing single-tenant
    program — weights AND per-batch stats — on the ragged wire, for both
    tenant-wire layouts."""
    single = StreamingLinearRegressionWithSGD()
    mt = TenantStackModel(
        1, step_size=single.default_step_size, wire_pack=wire_pack
    )
    for rb in _ragged_batches(unicode_mix=True):
        o1 = single.step(rb)
        o2 = mt.step(rb)
        for f in ("count", "mse", "real_stdev", "pred_stdev"):
            assert np.asarray(getattr(o1, f)).tobytes() == (
                np.asarray(getattr(o2, f))[0].tobytes()
            ), f
        assert np.array_equal(
            np.asarray(o1.predictions), np.asarray(o2.predictions)[0]
        )
    assert single.latest_weights.tobytes() == (
        mt.latest_weights[0].tobytes()
    )


def test_m1_bit_parity_padded_units_wire():
    single = StreamingLinearRegressionWithSGD()
    mt = TenantStackModel(1, step_size=single.default_step_size)
    for ub in _unit_batches():
        o1, o2 = single.step(ub), mt.step(ub)
        assert float(o1.mse) == float(o2.mse[0])
    assert single.latest_weights.tobytes() == mt.latest_weights[0].tobytes()


def test_m1_aggregate_output_is_passthrough():
    single = StreamingLinearRegressionWithSGD()
    mt = TenantStackModel(1, step_size=single.default_step_size)
    rb = _ragged_batches()[0]
    o1 = single.step(rb)
    import jax

    agg = aggregate_tenant_output(jax.device_get(mt.step(rb)), rb, mt)
    assert np.asarray(agg.mse).tobytes() == np.asarray(o1.mse).tobytes()
    assert np.array_equal(np.asarray(agg.predictions), np.asarray(o1.predictions))


# ---------------------------------------------------------------------------
# M>1: per-tenant parity, hyperparams, logistic residual


def test_m4_each_tenant_bit_equals_separate_model():
    """Routing moves rows, never semantics: tenant m's trajectory equals a
    standalone single-tenant model stepped on the routed sub-batches."""
    m = 4
    mt = TenantStackModel(m, step_size=0.1)
    singles = [StreamingLinearRegressionWithSGD(step_size=0.1) for _ in range(m)]
    for rb in _ragged_batches(unicode_mix=True):
        parts = split_batch_tenants(rb, tenant_route_keys(rb, m), m)
        out = mt.step(rb)
        for i in range(m):
            oi = singles[i].step(parts[i])
            assert float(oi.mse) == float(out.mse[i]), i
            assert float(oi.count) == float(out.count[i]), i
    for i in range(m):
        assert singles[i].latest_weights.tobytes() == (
            mt.latest_weights[i].tobytes()
        ), i


def test_m4_rung_agrees_with_the_top_rung_to_rounding():
    """Across rungs the results agree to f32 rounding (the reductions over
    rows run over fewer zeros): the stack fed each batch at the rung it
    calls for (128 rows a tenant) against the stack fed the parent's shape
    (256), weights and per-batch stats."""
    m = 4
    kw = dict(num_text_features=4096, l2_reg=0.1, step_size=0.005)
    at_rung, at_top = TenantStackModel(m, **kw), TenantStackModel(m, **kw)
    for rb in _ragged_batches(unicode_mix=True):
        o1 = at_rung.step(rb)
        assert np.asarray(o1.predictions).shape == (m, 128)
        o2 = at_top.step(at_top.prepare_wire_from_parts(
            at_top.split(rb, rung=rb.mask.shape[0])))
        assert np.asarray(o2.predictions).shape == (m, 256)
        assert np.array_equal(np.asarray(o1.count), np.asarray(o2.count))
        for f in ("mse", "real_stdev", "pred_stdev"):
            a = np.asarray(getattr(o1, f), np.float64)
            b = np.asarray(getattr(o2, f), np.float64)
            assert np.all(np.abs(a - b) <= 1e-6 * np.abs(b)), f
    w1 = at_rung.latest_weights.astype(np.float64)
    w2 = at_top.latest_weights.astype(np.float64)
    for i in range(m):
        assert np.abs(w1[i] - w2[i]).sum() <= 1e-6 * np.abs(w2[i]).sum(), i


# ---------------------------------------------------------------------------
# PR 49: a rung for the fullest tenant and a rung for the rest, ONE program


def _ids_by_counts(counts, shift=0):
    """Row r's tenant: the first ``counts[0]`` rows to tenant ``shift``, the
    next ``counts[1]`` to ``shift + 1`` (mod M), and so on."""
    m = len(counts)
    return ((np.repeat(np.arange(m), counts) + shift) % m).astype(np.int32)


@pytest.fixture(scope="module")
def batches_2048():
    return _ragged_batches(n=4096, b=2048, unicode_mix=True)


@pytest.mark.parametrize("counts,rungs", [
    pytest.param((1400, 200, 60, 388), (2048, 640), id="one_over_62.5pct"),
    pytest.param((2048, 0, 0, 0), (2048, 640), id="all_to_one"),
    pytest.param((700, 700, 300, 348), (1280, 1280), id="two_need_the_middle"),
    pytest.param((512, 512, 512, 512), (640, 640), id="even"),
])
def test_two_rungs_each_tenant_bit_equals_the_single_model_at_its_own_rung(
        batches_2048, counts, rungs):
    """The parity law on the two-rung plane: every tenant's weights and its
    row of the ONE StepOutput are bit-identical to a single model stepped on
    that tenant's part padded to its OWN rung. The second batch shifts the
    split by three tenants, so the fullest is tenant 0 and then tenant 3:
    the weights are gathered and written back by id."""
    m = 4
    mt = TenantStackModel(m, step_size=0.1)
    singles = [StreamingLinearRegressionWithSGD(step_size=0.1)
               for _ in range(m)]
    for shift, rb in zip((0, 3), batches_2048):
        ids = _ids_by_counts(counts, shift)
        parts = split_batch_tenants(rb, ids, m)
        fullest = shift % m
        assert [p.mask.shape[0] for p in parts] == [
            rungs[0] if i == fullest else rungs[1] for i in range(m)]
        wire = mt.prepare_wire_from_parts(parts)
        assert isinstance(wire, TwoRungWire) == (rungs[0] != rungs[1])
        out = mt.step(wire)
        assert np.asarray(out.predictions).shape == (m, rungs[0])
        for i in range(m):
            oi = singles[i].step(parts[i])
            for f in ("count", "mse", "real_stdev", "pred_stdev"):
                assert np.asarray(getattr(oi, f)).tobytes() == (
                    np.asarray(getattr(out, f))[i].tobytes()), (i, f)
            r = parts[i].mask.shape[0]
            got = np.asarray(out.predictions)[i]
            assert got[:r].tobytes() == np.asarray(oi.predictions).tobytes()
            assert not got[r:].any()        # the rest, padded to r_full
    for i in range(m):
        assert singles[i].latest_weights.tobytes() == (
            mt.latest_weights[i].tobytes()), i


def test_an_even_split_is_the_parents_parts_wire_and_program(batches_2048):
    """Equal rungs → what the plane did before it knew two: the M
    same-signature parts byte for byte, the ``[M, rung, ...]`` stacked wire,
    and — lowered text against lowered text — the ``lax.map`` of the step
    over the tenant axis and nothing else."""
    import jax
    from jax import lax

    rb = batches_2048[0]
    m = 4
    mt = TenantStackModel(m, num_text_features=4096, l2_reg=0.1)
    ids = tenant_route_keys(rb, m)
    parts = split_batch_tenants(rb, ids, m)
    before = split_batch_tenants(rb, ids, m, one_rung=True)
    assert {p.mask.shape[0] for p in parts} == {640}
    for p, q in zip(parts, before):
        for f in ("units", "offsets", "numeric", "label", "mask"):
            assert getattr(p, f).tobytes() == getattr(q, f).tobytes()
    wire = mt.prepare_wire(rb)
    assert isinstance(wire, RaggedUnitBatch) and wire.mask.shape == (m, 640)
    stacked = stack_batches(before)
    for f in ("units", "offsets", "numeric", "label", "mask"):
        assert getattr(wire, f).tobytes() == getattr(stacked, f).tobytes()

    def _mapped(weights, hyper, batch):     # the plane's program until PR 49
        with jax.named_scope("tenant_map"):
            return lax.map(
                lambda args: mt._one(*args), (weights, hyper, batch))

    args = (mt._weights, mt._hyper, wire)
    assert mt._prog_for(type(wire)).lower(*args).as_text() == (
        jax.jit(_mapped, donate_argnums=0).lower(*args).as_text())


def test_the_fullest_tenant_is_a_value_of_the_wire_not_a_program(
        batches_2048):
    """Tenant 0 the fullest, then tenant 3, same rungs and units buckets:
    ONE backend compile, one program held."""
    import jax.monitoring as mon

    from twtml_tpu.telemetry.trace import BACKEND_COMPILE_EVENT

    m = 4
    mt = TenantStackModel(m, num_text_features=4096, l2_reg=0.1)
    rb = batches_2048[0]
    wires = [
        mt.prepare_wire_from_parts(split_batch_tenants(
            rb, _ids_by_counts((1400, 200, 60, 388), shift), m))
        for shift in (0, 3)
    ]
    assert [int(w.ids[0]) for w in wires] == [0, 3]
    assert wires[1].ids.tolist() == [3, 0, 1, 2]
    seen = []

    def on_compile(event, _secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            seen.append(str(kw.get("fun_name", "")))

    mon.register_event_duration_secs_listener(on_compile)
    try:
        counts = [np.asarray(mt.step(w).count).tolist() for w in wires]
    finally:
        mon.unregister_event_duration_listener(on_compile)
    assert counts == [[1400, 200, 60, 388], [200, 60, 388, 1400]]
    assert [f for f in seen if "_mapped" in f] == ["jit(_mapped)"], seen
    assert set(mt._progs) == {TwoRungWire}
    assert mt._prog_for(TwoRungWire)._cache_size() == 1


@pytest.mark.parametrize("how", ["pinned_rung", "group_wire", "mesh_1d"])
def test_one_rung_for_all_where_the_wire_or_program_has_one_shape(
        batches_2048, how):
    """A pinned ``rung=``, ``--wirePack group`` and a 1D mesh keep the
    fullest tenant's rung for every part of a lopsided split."""
    import jax

    from twtml_tpu.features.batch import PackedBatch
    from twtml_tpu.parallel import make_mesh

    rb, m = batches_2048[0], 4
    kw = dict(num_text_features=4096, l2_reg=0.1, tenant_key="lang")
    ids = tenant_route_keys(rb, m, "lang")
    assert np.bincount(ids, minlength=m)[0] > 1280      # tenant 0: top rung
    two = {p.mask.shape[0] for p in split_batch_tenants(rb, ids, m)}
    assert len(two) == 2 and max(two) == 2048
    if how == "pinned_rung":
        model, rung = TenantStackModel(m, **kw), 2048
    elif how == "group_wire":
        model, rung = TenantStackModel(m, wire_pack="group", **kw), 0
    else:
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
        model, rung = TenantStackModel(m, mesh=mesh, **kw), 0
    parts = model.split(rb, rung=rung)
    assert {p.mask.shape[0] for p in parts} == {2048}
    wire = model.prepare_wire_from_parts(parts)
    assert isinstance(wire, PackedBatch if how == "group_wire"
                      else RaggedUnitBatch)
    out = model.step(wire)
    assert np.asarray(out.predictions).shape == (m, 2048)
    assert np.asarray(out.count).tolist() == np.bincount(
        ids, minlength=m).tolist()


def test_per_tenant_hyperparams_are_mapped_leaves():
    """Per-tenant step sizes: tenant i bit-equals a single model built with
    THAT step size on the same routed rows."""
    m = 2
    mt = TenantStackModel(m, step_sizes=[0.05, 0.2])
    singles = [
        StreamingLinearRegressionWithSGD(step_size=s) for s in (0.05, 0.2)
    ]
    for rb in _ragged_batches():
        parts = split_batch_tenants(rb, tenant_route_keys(rb, m), m)
        mt.step(rb)
        for i in range(m):
            singles[i].step(parts[i])
    for i in range(m):
        assert singles[i].latest_weights.tobytes() == (
            mt.latest_weights[i].tobytes()
        ), i


def test_logistic_residual_rides_the_stack():
    m = 2
    lr = StreamingLogisticRegressionWithSGD
    mt = TenantStackModel(
        m,
        step_size=lr.default_step_size,
        residual_fn=lr.residual_fn,
        prediction_fn=lr.prediction_fn,
        round_predictions=lr.round_predictions,
    )
    singles = [lr() for _ in range(m)]
    rb = _ragged_batches()[0]
    parts = split_batch_tenants(rb, tenant_route_keys(rb, m), m)
    out = mt.step(rb)
    for i in range(m):
        oi = singles[i].step(parts[i])
        assert float(oi.mse) == float(out.mse[i])
    for i in range(m):
        assert singles[i].latest_weights.tobytes() == (
            mt.latest_weights[i].tobytes()
        )


def test_dry_tenant_stats_stay_finite_and_weights_frozen():
    """An all-padding tenant batch is a weight no-op with finite stats —
    the healthy-path guarantee the sentinel's aggregate check relies on."""
    mt = TenantStackModel(4)
    rb = _ragged_batches()[0]
    ids = np.zeros(rb.mask.shape[0], np.int32)  # tenants 1..3 dry
    wire = mt.prepare_wire_from_parts(split_batch_tenants(rb, ids, 4))
    out = mt.step(wire)
    host = np.asarray(out.mse)
    assert np.isfinite(host).all()
    assert float(np.asarray(out.count)[1]) == 0.0
    w = mt.latest_weights
    assert np.array_equal(w[1], np.zeros_like(w[1]))  # dry → untouched
    assert not np.array_equal(w[0], np.zeros_like(w[0]))


def test_aggregate_output_m4_exact_counts_and_mse():
    import jax

    mt = TenantStackModel(4)
    rb = _ragged_batches()[0]
    out = jax.device_get(mt.step(rb))
    agg = aggregate_tenant_output(out, rb, mt)
    # fetched at the rung, delivered at the ORIGINAL batch's row count
    assert np.asarray(out.predictions).shape == (4, 128)
    assert np.asarray(agg.predictions).shape == rb.mask.shape == (256,)
    counts = np.asarray(out.count, np.float64)
    assert float(agg.count) == counts.sum()
    want_mse = (counts * np.asarray(out.mse, np.float64)).sum() / counts.sum()
    # agg.mse is stored f32; compare at f32 resolution of the magnitude
    assert abs(float(agg.mse) - want_mse) <= max(1e-3, 1e-6 * want_mse)
    # predictions return in ORIGINAL row order: check against a per-tenant
    # manual scatter through the same deterministic route
    rows_per = tenant_rows(rb, mt.route_ids(rb), 4)
    for m, rows in enumerate(rows_per):
        assert np.array_equal(
            np.asarray(agg.predictions)[rows],
            np.asarray(out.predictions)[m][: rows.shape[0]],
        )
    # ... which is each row's prediction by ITS tenant's single model
    singles = [StreamingLinearRegressionWithSGD() for _ in range(4)]
    for single, part, rows in zip(singles, mt.split(rb), rows_per):
        assert np.array_equal(
            np.asarray(agg.predictions)[rows],
            np.asarray(single.step(part).predictions)[: rows.shape[0]],
        )


def test_nonfinite_tenant_poisons_the_aggregate():
    """One poisoned tenant must surface in the aggregate scalars — that is
    what routes the existing divergence sentinel onto the stacked plane."""
    import jax

    mt = TenantStackModel(2)
    rb = _ragged_batches()[0]
    out = jax.device_get(mt.step(rb))
    poisoned = out._replace(
        mse=np.array([out.mse[0], np.nan], np.float32)
    )
    agg = aggregate_tenant_output(poisoned, rb, mt)
    assert not np.isfinite(float(agg.mse))


def test_split_tenant_output_views():
    import jax

    mt = TenantStackModel(3)
    rb = _ragged_batches()[0]
    out = jax.device_get(mt.step(rb))
    parts = split_tenant_output(out, 3)
    assert len(parts) == 3
    for i, p in enumerate(parts):
        assert float(p.mse) == float(out.mse[i])


def test_checkpoint_roundtrip_and_flat_broadcast():
    mt = TenantStackModel(3)
    for rb in _ragged_batches():
        mt.step(rb)
    state = mt.latest_weights
    fresh = TenantStackModel(3)
    fresh.set_initial_weights(state)
    assert fresh.latest_weights.tobytes() == state.tobytes()
    # the sentinel's flat zeros reset broadcasts across tenants
    fresh.set_initial_weights(np.zeros(state.shape[1], np.float32))
    assert not fresh.latest_weights.any()


# ---------------------------------------------------------------------------
# mesh composition


def test_mesh_data_axis_composes(monkeypatch):
    import jax

    from twtml_tpu.parallel import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    m = 4
    ref = TenantStackModel(m)
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    mtm = TenantStackModel(m, mesh=mesh)
    mtg = TenantStackModel(m, mesh=mesh, wire_pack="group")
    for rb in _ragged_batches():
        ref.step(rb)
        mtm.step(rb)
        mtg.step(rb)
    # group wire bit-equals the stacked wire on the mesh (same program law)
    assert mtm.latest_weights.tobytes() == mtg.latest_weights.tobytes()
    # mesh vs single-device: same math, different psum association
    assert np.allclose(
        mtm.latest_weights, ref.latest_weights, rtol=1e-5, atol=1e-4
    )


def test_mesh_parts_share_one_unit_capacity_and_a_rung_of_the_axis():
    """Rows over ``data``: the rung is a multiple of the axis, and parts
    whose shards need different unit capacities align to ONE (the
    fullest's), so they stack; the result is the unsharded plane's."""
    import jax

    from twtml_tpu.parallel import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    rb, ids = _rung_batch(), _ids_units_over()
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    ref, mtm = TenantStackModel(4), TenantStackModel(4, mesh=mesh)
    for model in (ref, mtm):
        parts = split_batch_tenants(
            rb, ids, 4, row_multiple=getattr(model, "num_data", 1),
            one_rung=True)
        wire = model.prepare_wire_from_parts(parts)
        assert wire.mask.shape == (4, 768)
        out = model.step(wire)
    assert wire.num_shards == 2 and wire.units.shape == (4, 2 * 8192)
    assert np.asarray(out.count).tolist() == [300.0, 242.0, 241.0, 241.0]
    assert np.allclose(
        mtm.latest_weights, ref.latest_weights, rtol=1e-5, atol=1e-4
    )


def test_mesh_2d_tenant_axis_shards_tenants():
    import jax

    from twtml_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    m = 4
    mesh2 = make_mesh(num_data=2, num_model=2, devices=jax.devices()[:4])
    mt2d = TenantStackModel(m, mesh=mesh2)
    mesh1 = make_mesh(num_data=2, devices=jax.devices()[:2])
    mt1d = TenantStackModel(m, mesh=mesh1)
    for rb in _ragged_batches():
        mt2d.step(rb)
        mt1d.step(rb)
    from jax.sharding import PartitionSpec as P

    assert mt2d._weights.sharding.spec == P("model", None)
    assert np.allclose(
        mt2d.latest_weights, mt1d.latest_weights, rtol=1e-5, atol=1e-4
    )


# ---------------------------------------------------------------------------
# app-level acceptance: one fetch per tick at M=8, M=1 app parity


CLOSED = "http://127.0.0.1:9"
BASE = [
    "--source", "replay", "--seconds", "0", "--backend", "cpu",
    "--batchBucket", "16", "--tokenBucket", "64", "--master", "local[1]",
    "--lightning", CLOSED, "--twtweb", CLOSED, "--webTimeout", "0.2",
]


def _corpus_file(tmp_path, total=8 * 16, seed=51):

    path = tmp_path / "tweets.jsonl"
    with open(path, "w") as fh:
        for s in SyntheticSource(total=total, seed=seed, base_ms=NOW_MS).produce():
            fh.write(json.dumps(s.to_json()) + "\n")
    return path


def _run_counting_fetches(conf_args):
    import jax

    from twtml_tpu.apps import linear_regression as app

    jax.devices()
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    jax.device_get = counting
    try:
        totals = app.run(ConfArguments().parse(list(conf_args)))
    finally:
        jax.device_get = real
    return totals, calls["n"]


def test_app_m8_one_fetch_per_tick(tmp_path, monkeypatch):
    """ACCEPTANCE: a real M=8 app run fetches ONCE per dispatched batch —
    fetch count is independent of the tenant count (the whole point), and
    per-tenant rows conserve into the telemetry view."""
    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)
    totals, fetches = _run_counting_fetches(
        BASE + ["--replayFile", str(path), "--tenants", "8"]
    )
    assert totals["batches"] == 8
    assert totals["tenants"] == 8
    assert fetches == 8  # ONE device_get per tick, M=8 notwithstanding
    view = _tenants_tel.last_tenants()
    assert view is not None and len(view["tenants"]) == 8
    # row conservation across the whole run
    assert sum(t["rows"] for t in view["tenants"]) == totals["count"] == 128
    assert view["gating"] == max(
        view["tenants"], key=lambda t: t["batch"]
    )["tenant"]
    reg = _metrics.get_registry().snapshot()
    assert reg["gauges"]["tenants.configured"] == 8


def test_app_m1_bit_parity_with_single_tenant_run(tmp_path, monkeypatch):
    """ACCEPTANCE: --tenants 1 produces byte-identical final weights AND
    published stats (the printed per-batch lines are the published stats)
    to a run without the flag."""
    import contextlib
    import io

    from twtml_tpu.apps import linear_regression as app
    from twtml_tpu.checkpoint import Checkpointer

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)

    def run(extra, ckdir):
        _metrics.reset_for_tests()
        _tenants_tel.reset_for_tests()
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            totals = app.run(ConfArguments().parse(
                BASE + ["--replayFile", str(path),
                        "--checkpointDir", str(ckdir),
                        "--checkpointEvery", "1"] + extra
            ))
        return totals, buf.getvalue()

    t1, out1 = run([], tmp_path / "ck_single")
    # TWTML_FORCE_TENANT_PLANE routes --tenants 1 through the stacked
    # program (the default path keeps the plain model — a 1-tenant
    # stream must not pay the routing split)
    monkeypatch.setenv("TWTML_FORCE_TENANT_PLANE", "1")
    t2, out2 = run(["--tenants", "1"], tmp_path / "ck_m1")
    monkeypatch.delenv("TWTML_FORCE_TENANT_PLANE")
    assert t1["batches"] == t2["batches"]
    assert out1 == out2  # published stats line-for-line identical
    w1, _ = Checkpointer(str(tmp_path / "ck_single")).restore()
    w2, _ = Checkpointer(str(tmp_path / "ck_m1")).restore()
    assert np.asarray(w1).tobytes() == np.asarray(w2)[0].tobytes()


def test_app_m4_sentinel_rolls_back_stacked_plane(tmp_path, monkeypatch):
    """A poisoned batch on the tenant plane: the aggregate stats go
    non-finite, the sentinel skips the batch and rolls the WHOLE stacked
    state back to the verified checkpoint — one guard for M models."""
    from twtml_tpu.streaming import faults

    monkeypatch.setenv("TWTML_NOW_MS", str(NOW_MS))
    path = _corpus_file(tmp_path)
    try:
        totals, fetches = _run_counting_fetches(
            BASE + ["--replayFile", str(path), "--tenants", "4",
                    "--checkpointDir", str(tmp_path / "ck"),
                    "--checkpointEvery", "1", "--chaos", "source.nan@5"]
        )
    finally:
        faults.uninstall_chaos()
    reg = _metrics.get_registry()
    assert reg.counter("model.rollbacks").snapshot() == 1
    # the sentinel skips the poisoned batch, and the r21 intake journal
    # replays its rows from disk (the journal seam sits upstream of the
    # poison injection point, so they re-featurize clean): all 8 batches
    # of the corpus end up trained, zero rows lost
    assert totals["batches"] == 8
    assert totals["count"] == 128
    assert reg.counter("model.rows_lost").snapshot() == 0
    assert reg.counter("journal.replayed_rows").snapshot() > 0
    # zero ADDED fetches: sentinel reads fetched stats — one fetch per
    # DISPATCHED batch (8 original + 1 replayed)
    assert fetches == 9


def test_conf_flags():
    conf = ConfArguments().parse(["--tenants", "4", "--tenantKey", "lang"])
    assert conf.tenants == 4 and conf.tenantKey == "lang"
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--tenantKey", "bogus"])
    with pytest.raises(SystemExit):
        ConfArguments().parse(["--tenants", "0"])
