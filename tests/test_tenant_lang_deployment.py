"""The tenant plane under the SCRIPT key (``--tenants 4 --tenantKey lang``,
PR 42): a plain reference of it (``benchmark/reference/
tenant_lang_linear_sgd.py``: NumPy, float64, its own copy of the rule)
against the program — the rule's two copies on both forms of the ragged
wire, the app end to end with a dry tenant, the row ladder under a lopsided
split, what the plane leaves under ``--trace`` under each key, and a
checkpoint trained under the key served by ``apps/serve --abtest on``. The
cell ``hash2e18-lang4-trimmed-280`` runs this key on the chip
(``benchmark/configs/hash2e18-lang4.json``; its harness-level fault cases are
``benchmark/tests/test_hash2e18_lang4.py``, these are their in-process
twins).
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from benchmark import gen
from benchmark import spans as span_files
from benchmark.reference import tenant_lang_linear_sgd as ref
from test_tenant_deployment import (
    F_TEXT,
    MODEL,
    _generator,
    _run_app,
    _stream,
    _weights,
)
from twtml_tpu.features.batch import (
    RAGGED_UNIT_MULTIPLE,
    RaggedUnitBatch,
    split_batch_tenants,
    tenant_route_keys,
    tenant_row_rungs,
)
from twtml_tpu.telemetry import metrics as _metrics
from twtml_tpu.telemetry import tenants as _tenants_tel

LANG = ["--tenants", "4", "--tenantKey", "lang"]


@pytest.fixture(autouse=True)
def _fresh_registries():
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()
    yield
    _metrics.reset_for_tests()
    _tenants_tel.reset_for_tests()


def _ragged(texts, dtype, slack=0, pad_rows=0) -> RaggedUnitBatch:
    """Texts AS THE WIRE CARRIES THEM in one flat buffer of ``dtype``,
    ``slack`` zero units after the last row's and ``pad_rows`` all-padding
    rows after the last text."""
    units = [np.frombuffer(t.encode("utf-16-le", "surrogatepass"), "<u2")
             for t in texts]
    n = len(texts) + pad_rows
    offsets = np.full(n + 1, sum(u.size for u in units), np.int32)
    offsets[0] = 0
    np.cumsum([u.size for u in units], out=offsets[1:len(texts) + 1])
    flat = np.concatenate(units + [np.zeros(slack, "<u2")])
    assert flat.max(initial=0) <= np.iinfo(dtype).max
    mask = np.zeros(n, np.float32)
    mask[:len(texts)] = 1.0
    return RaggedUnitBatch(
        flat.astype(dtype), offsets, np.zeros((n, 4), np.float32),
        np.zeros(n, np.float32), mask, row_len=512,
    )


def _texts(n: int, seed: int, narrow: bool) -> list:
    """Capitals, accents (upper-case ones that ``str.lower`` moves inside
    Latin-1, and ``Ÿ`` which it moves INTO it), CJK, surrogate pairs, ``İ``
    (lowered to two units, one of them U+0307) and an empty text. ``narrow``
    keeps every wire unit under 256: the one-byte form of the ragged wire."""
    rng = np.random.default_rng(seed)
    pools = [
        "abcdefghijklmnopqrstuvwxyz  ABCDEFGHIJ#@:/.'",
        "abc défg ÉÀÖ ñç ß Ÿ ü",
    ]
    if not narrow:
        pools += [
            "ab İ c", "中文字 かな 漢 丁 倀 儿", "ab \U0001f600\U0001f61e c",
            "é \U0001f64f 中", "ă ő ǆ ɐ",
        ]
    out = [""]
    for i in range(1, n):
        pool = pools[0] if i % 10 < 7 else pools[1 + i % (len(pools) - 1)]
        out.append("".join(rng.choice(list(pool), rng.integers(1, 281))))
    return out


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
@pytest.mark.parametrize("tenants", [2, 4, 8])
def test_lang_rules_two_copies_agree_on_1e4_rows(tenants, dtype):
    """``features/batch.tenant_route_keys(mode="lang")`` over the units the
    ragged wire carries, in its one-byte and its two-byte form, against the
    reference's rule written out per text."""
    texts = _texts(10_000, 17, narrow=dtype is np.uint8)
    rb = _ragged([ref.wire_text(t) for t in texts], dtype, slack=100)
    got = tenant_route_keys(rb, tenants, "lang")
    want = np.array([ref.route(t, tenants) for t in texts])
    assert np.array_equal(got, want)
    assert got[0] == 0 and ref.script_class("") == 0     # the empty text
    seen = np.bincount(want, minlength=tenants)
    assert seen[0] > 0.6 * len(texts) and seen[1] > 0    # ASCII, accents
    if dtype is np.uint16:   # one script over several tenants (the finding)
        assert (seen > 0).sum() >= min(tenants, 4)


def test_the_rule_reads_the_lowered_text_and_surrogates_as_units():
    assert ref.script_class("Good Morning") == 0
    assert ref.script_class("café") == 1 and ref.script_class("CAFÉ") == 1
    # str.lower moves these two ACROSS classes: the key reads the wire
    assert ref.script_class("İ") == 1 + 0x03      # i + U+0307
    assert ref.script_class("Ÿ") == 1             # ÿ, U+00FF
    assert ref.script_class("中") == 1 + 0x4E
    # an emoji is its two surrogates; the LOW one (U+DE00) is the larger
    assert ref.script_class("a\U0001f600") == 1 + 0xDE
    assert ref.route("a\U0001f600", 4) == 3 and ref.route("丁 中", 4) == 3
    # one script, several tenants: the high byte of the largest ideograph
    assert {ref.route(c, 4) for c in "中倀儿叀"} == {0, 1, 2, 3}


def _parent_lang_ids(units, offsets, m):
    """The ``lang`` branch as it stood before PR 42 (a ``uint64`` copy of
    the whole buffer, ``reduceat`` over every row's clipped start)."""
    units = np.asarray(units, np.uint64)
    offs = np.asarray(offsets, np.int64)
    lengths = offs[1:] - offs[:-1]
    safe = np.minimum(offs[:-1], units.shape[0] - 1)
    maxs = np.maximum.reduceat(units, safe)
    maxs = np.where(lengths > 0, maxs, np.uint64(0))
    cls = np.where(maxs < 128, np.uint64(0),
                   np.uint64(1) + (maxs >> np.uint64(8)))
    return (cls % np.uint64(m)).astype(np.int32)


@pytest.mark.parametrize("seed", range(6))
def test_row_maxima_on_the_units_own_dtype_give_the_parents_ids(seed):
    """The repair of PR 42: no widened copy, identical ids — on random
    buffers of both widths with empty rows inside, padding rows behind and
    the bucket's slack after the last unit."""
    rng = np.random.default_rng(seed)
    narrow = bool(seed % 2)
    texts = _texts(int(rng.integers(1, 400)), 100 + seed, narrow)
    rng.shuffle(texts)
    rb = _ragged(
        [ref.wire_text(t) for t in texts], np.uint8 if narrow else np.uint16,
        slack=int(rng.integers(1, 64)), pad_rows=int(rng.integers(0, 9)),
    )
    for m in (2, 4, 8):
        got = tenant_route_keys(rb, m, "lang")
        assert got.dtype == np.int32
        assert np.array_equal(got, _parent_lang_ids(rb.units, rb.offsets, m))


def test_a_full_buffer_before_padding_rows_keeps_the_last_rows_largest_unit():
    """Where the two differ, the parent was wrong by its own rule: with the
    units buffer EXACTLY full and a padding row behind the last text, the
    clipped start of the padding row cut the last text's last unit off its
    maximum (one wire in ``RAGGED_UNIT_MULTIPLE`` with a short batch; no
    full batch has a padding row)."""
    rb = _ragged(["ab", "cd中"], np.uint16, slack=0, pad_rows=1)
    assert rb.units.shape[0] == int(rb.offsets[-1])
    want = [ref.route("ab", 4), ref.route("cd中", 4), 0]
    assert want[1] == 3
    assert tenant_route_keys(rb, 4, "lang").tolist() == want
    assert _parent_lang_ids(rb.units, rb.offsets, 4).tolist() == [0, 0, 0]


def _dry_seed(rows, batches, tenants) -> int:
    """The first seed whose stream leaves some tenant without a row in a
    batch AFTER the first, by the reference's own routing."""
    for seed in range(1, 200):
        g = _generator(rows, batches)
        chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0,
                               rows * batches)
        ids = np.array([ref.route(t, tenants) for t in chunk.text])
        per_batch = [np.bincount(ids[b * rows:(b + 1) * rows],
                                 minlength=tenants) for b in range(batches)]
        if any(0 in c for c in per_batch[1:]) and per_batch[0].all():
            return seed
    raise AssertionError("no seed leaves a tenant dry")


def test_reference_against_the_plane_through_the_app_with_a_dry_tenant(
        tmp_path, monkeypatch):
    rows, batches = 64, 4
    seed = _dry_seed(rows, batches, 4)
    g, chunk, path = _stream(tmp_path, rows, batches, seed)
    ckpt = str(tmp_path / "ck")
    totals, printed = _run_app(monkeypatch, path, ckpt, rows, batches, LANG)
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches,
        model=dict(MODEL, tenants=4), generator=g)
    assert totals["batches"] == batches and totals["tenants"] == 4
    assert [p["batch"] for p in printed] == [s["count"] for s in stats]
    assert [p["count"] for p in printed] == [
        rows * (b + 1) for b in range(batches)]
    assert any(0 in s["tenant_rows"] for s in stats[1:])
    # the skew the deployment is about: one tenant holds most of each batch
    assert all(s["tenant_rows"][0] > rows // 2 for s in stats)
    for p, s in zip(printed, stats):   # both sides print a HALF_UP integer
        assert abs(p["mse"] - s["mse"]) <= 1.0
    w = _weights(ckpt)
    assert w.shape == learner.w.shape == (4, F_TEXT + 4)
    # float32 against float64: ~2e-7; a dry tenant's L2 shrink alone ~2e-3
    assert np.abs(w - learner.w).sum() / np.abs(learner.w).sum() < 5e-6


def test_the_hash_rule_in_the_lang_rules_place_is_seen(tmp_path, monkeypatch):
    """A program that routes by the OTHER key trains other models: counts
    stay right, the ``[M, F+4]`` weights do not."""
    g, chunk, path = _stream(tmp_path, 64, 3, 7)
    ckpt = str(tmp_path / "ck")
    _totals, printed = _run_app(monkeypatch, path, ckpt, 64, 3,
                                ["--tenants", "4"])          # hash key
    learner, stats = ref.train_on_chunks(
        [chunk], batch_rows=64, n_batches=3, model=dict(MODEL, tenants=4),
        generator=g)
    assert [p["batch"] for p in printed] == [s["count"] for s in stats]
    w = _weights(ckpt)
    assert np.abs(w - learner.w).sum() / np.abs(learner.w).sum() > 0.5


def _skewed(rows0: int, long0: int = 2):
    """A 2,048-row batch whose first ``rows0`` rows go to tenant 0 (texts of
    ``long0`` units) and the rest in turn to tenants 1–3 (two units)."""
    b = 2048
    texts = ["a" * long0] * rows0 + ["bc"] * (b - rows0)
    total = sum(len(t) for t in texts)
    rb = _ragged(texts, np.uint8, slack=(-total) % RAGGED_UNIT_MULTIPLE)
    ids = np.zeros(b, np.int32)
    ids[rows0:] = 1 + np.arange(b - rows0) % 3
    return rb, ids


@pytest.mark.parametrize("rows0,long0,rung,rest", [
    (1280, 2, 1280, 640),   # the fullest tenant fills the middle rung exactly
    (1281, 2, 2048, 640),   # one row more: the top rung, the batch's own rows
    (1200, 40, 2048, 640),  # rows that fit 1,280 whose units do not: the next
    (500, 2, 640, 640),     # an even-ish split: the first rung for all four
])
def test_the_ladder_under_skew(rows0, long0, rung, rest):
    """The fullest tenant's part at the rung IT calls for, the other three
    at the rung the fullest of THEM calls for (256–516 rows of two units:
    the first); ``one_rung`` is the split until PR 49, all at the
    fullest's."""
    assert tenant_row_rungs(2048, 4) == (640, 1280, 2048)
    rb, ids = _skewed(rows0, long0)
    parts = split_batch_tenants(rb, ids, 4)
    assert [p.mask.shape[0] for p in parts] == [rung, rest, rest, rest]
    assert {p.mask.shape[0] for p in split_batch_tenants(
        rb, ids, 4, one_rung=True)} == {rung}
    assert [p.num_valid for p in parts] == np.bincount(
        ids, minlength=4).tolist()

    def scaled(r):       # a lower rung scales the buffer, in whole buckets
        return (-(-rb.units.shape[0] * r // 2048 // RAGGED_UNIT_MULTIPLE)
                * RAGGED_UNIT_MULTIPLE)

    if rung == 2048:     # the top rung keeps the parent's units buffer
        assert parts[0].units.shape[0] == rb.units.shape[0]
        assert parts[0].units[:rows0 * long0].tobytes() == (
            rb.units[:rows0 * long0].tobytes())
    else:
        assert parts[0].units.shape[0] == scaled(rung)
    assert {p.units.shape[0] for p in parts[1:]} == {scaled(rest)}


def test_a_lopsided_stream_holds_one_program_a_units_bucket():
    """Over a pool whose batches fall into several units buckets of the
    ragged wire the plane holds ONE program a bucket of the TWO-RUNG wire
    (``_two_rung_units``: the parent's rounded up to a sixteenth of the next
    power of two, so no more than the parent's) and no more: both halves in
    it, never a program a rung beside a program a pair, and never the
    ``[M, B]`` map of the whole batch's shape, which no batch called for."""
    import json

    from twtml_tpu.features.batch import TwoRungWire, _two_rung_units
    from twtml_tpu.features.featurizer import Featurizer, Status
    from twtml_tpu.parallel import TenantStackModel

    m, rows, batches = 4, 256, 8
    g = _generator(rows, batches)
    chunk = gen.make_chunk(g, gen.build_vocab(g, 3), 3, 0, rows * batches)
    feat = Featurizer(now_ms=g["now_ms"])
    statuses = [Status.from_json(json.loads(line)) for line in chunk.lines]
    stack = TenantStackModel(m, num_text_features=F_TEXT, l2_reg=0.1,
                             step_size=0.005, tenant_key="lang")
    buckets, wires = set(), set()
    for b in range(batches):
        rb = feat.featurize_batch_ragged(
            statuses[b * rows:(b + 1) * rows], row_bucket=rows,
            pre_filtered=True)
        wire = stack.prepare_wire(rb)
        assert isinstance(wire, TwoRungWire)
        assert wire.full.mask.shape == (1, 256)
        coarse = _two_rung_units(rb.units.shape[0])
        assert rb.units.shape[0] <= coarse < rb.units.shape[0] * 9 / 8
        assert wire.full.units.shape == (1, coarse)
        half = -(-coarse // 2 // RAGGED_UNIT_MULTIPLE) * RAGGED_UNIT_MULTIPLE
        assert wire.rest.units.shape == (m - 1, half)
        assert wire.rest.mask.shape == (m - 1, 128)
        buckets.add((rb.units.shape[0], str(rb.units.dtype)))
        wires.add((wire.full.units.shape, wire.rest.units.shape,
                   str(wire.full.units.dtype)))
        out = stack.step(wire)
        assert np.asarray(out.predictions).shape == (m, 256)
    assert len(buckets) > 1                 # the stream DOES change bucket
    assert set(stack._progs) == {TwoRungWire}
    assert stack._prog_for(TwoRungWire)._cache_size() == len(wires) <= len(
        buckets)
    # at the cell's size the 280-unit mix's four buckets are ONE
    assert {_two_rung_units(n) for n in (303104, 307200, 311296, 315392)
            } == {327680}


def test_a_near_dry_tenants_part_takes_its_own_gram_plane():
    """Every part of the top rung has the batch's own rows, but
    ``ops/gram.text_gram``'s gate reads each part's VALID rows: a tenant
    whose few rows are all short (token mass <= 127) takes the s8 plane
    while the others take bf16. On the chip that part's step is ~6 ms
    shorter at 2,048 rows (PERF.md section 6, PR 42)."""
    from twtml_tpu.parallel import TenantStackModel

    rng = np.random.default_rng(0)

    def ascii_text(n):
        return "".join(rng.choice(list("abcdefghij klmnop"), n))

    texts = [ascii_text(int(rng.integers(20, 281))) for _ in range(200)]
    texts += ["café " + ascii_text(int(rng.integers(20, 281)))
              for _ in range(52)]
    texts += ["ă " + ascii_text(40)]                      # tenant 2: one row
    texts += ["中 " + ascii_text(int(rng.integers(150, 281)))
              for _ in range(3)]                          # tenant 3: long rows
    total = sum(len(t) for t in texts)
    rb = _ragged(texts, np.uint16, slack=(-total) % RAGGED_UNIT_MULTIPLE)
    model = TenantStackModel(4, num_text_features=1 << 16, l2_reg=0.1,
                             step_size=0.005, quality=True, tenant_key="lang")
    out = model.step(model.prepare_wire(rb))
    assert np.asarray(out.count).tolist() == [200, 52, 1, 3]
    assert np.asarray(out.predictions).shape == (4, 256)      # the top rung
    # the quality vector's last field: 1 = bf16, 2 = s8 (ops/gram.py)
    assert np.asarray(out.quality)[:, -1].tolist() == [1, 1, 2, 1]


@pytest.mark.parametrize("key,bucket,watch", [
    ("lang", 256, "on"), ("hash", 128, "on"), ("lang", 256, "off")])
def test_the_planes_spans_say_the_rung_under_each_key(
        tmp_path, monkeypatch, key, bucket, watch):
    """256 rows over 4 tenants: the ladder is 128 / 256. The hash key takes
    the first rung for all four; the script key gives tenant 0 more than 128
    rows in every batch, so ITS part is padded to the batch's own 256 rows
    and the three others to 128 (PR 49): ``bucket`` is the fullest part's
    rung, ``buckets`` all four in tenant order, ``pad_rows`` their sum less
    the rows, and the ``tenant_split`` span says the two rungs. Each part's
    own Gram plane rides the quality leaf, so ``--modelWatch off`` (no such
    leaf in the fetch) leaves ``planes`` out and the rest as it is. ONE
    fetch and ONE program call a batch under either key."""
    import jax

    from twtml_tpu.parallel import TenantStackModel

    calls = {"fetch": 0, "program": 0}
    real_get, real_prog = jax.device_get, TenantStackModel._prog_for

    def counting_get(x):
        calls["fetch"] += 1
        return real_get(x)

    def counting_prog(self, batch_cls):
        fn = real_prog(self, batch_cls)

        def call(*args):
            calls["program"] += 1
            return fn(*args)
        return call

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(TenantStackModel, "_prog_for", counting_prog)
    rows, batches = 256, 3
    _g, _chunk, path = _stream(tmp_path, rows, batches, 5)
    trace = str(tmp_path / "spans.json")
    _run_app(monkeypatch, path, str(tmp_path / "ck"), rows, batches,
             ["--tenants", "4", "--tenantKey", key, "--trace", trace,
              "--modelWatch", watch, "--numTextFeatures", "65536"])
    events = span_files.load_events(trace)
    split = [e for e in events if e.get("name") == "tenant_split"]
    routed = [e for e in events if e.get("name") == "tenant_rows"]
    assert len(split) == len(routed) == batches
    assert calls == {"fetch": batches, "program": batches}
    buckets = [256, 128, 128, 128] if key == "lang" else [128] * 4
    for e in split:
        a = e["args"]
        assert a["tenants"] == 4 and a["rows"] == rows and a["bytes"] > 0
        assert a["rungs"] == [buckets[0], buckets[-1]]
    for e in routed:
        a = e["args"]
        assert a["bucket"] == bucket == max(a["buckets"])
        assert a["buckets"] == buckets
        assert sum(a["rows"]) == rows
        assert all(n <= r for n, r in zip(a["rows"], a["buckets"]))
        assert a["pad_rows"] == sum(buckets) - rows
        if watch == "off":
            assert "planes" not in a
        else:
            # at 2^16 dims the step takes the Gram path: bf16 for a part
            # with long rows, s8 for one whose few rows are all short
            assert len(a["planes"]) == 4 and set(a["planes"]) <= {1, 2}
            assert a["planes"][0] == 1
            if key == "hash":      # ~64 rows a part, long ones in each
                assert a["planes"] == [1, 1, 1, 1]
    if (key, watch) == ("lang", "on"):
        assert any(2 in e["args"]["planes"] for e in routed)
        if key == "lang":
            assert a["rows"][0] > rows // 2


def test_a_checkpoint_trained_under_the_key_serves_under_abtest(
        tmp_path, monkeypatch):
    """The stacked ``[4, F+4]`` checkpoint the trainer wrote under
    ``--tenantKey lang`` loads in ``apps/serve --abtest on``: one tenant
    answers every row as champion, by the reference's own weights of that
    tenant, and all four are scored in its shadow."""
    from benchmark.reference import linear_sgd
    from twtml_tpu.apps import serve as serve_app
    from twtml_tpu.serving.client import ServingClient

    rows, batches = 64, 3
    g, chunk, path = _stream(tmp_path, rows, batches, 7)
    ckpt = str(tmp_path / "ck")
    _run_app(monkeypatch, path, ckpt, rows, batches, LANG)
    learner, _stats = ref.train_on_chunks(
        [chunk], batch_rows=rows, n_batches=batches,
        model=dict(MODEL, tenants=4), generator=g)

    stop, up, ready, result = threading.Event(), threading.Event(), {}, {}

    def started(server, plane, promoter):
        ready["port"] = server._runner.addresses[0][1]
        up.set()

    from twtml_tpu.config import ConfArguments

    conf = ConfArguments().parse([
        "--backend", "cpu", "--master", "local[1]", "--checkpointDir", ckpt,
        "--numTextFeatures", str(F_TEXT), "--servePort", "0",
        "--serveBatchRows", "32", "--serveMaxWaitMs", "2",
        "--servePromoteEvery", "600", "--abtest", "on",
    ])
    thread = threading.Thread(target=lambda: result.update(
        stats=serve_app.run(conf, started=started, stop_event=stop)))
    thread.start()
    try:
        assert up.wait(timeout=300), "serve app never came up"
        picked = list(range(8))
        res = ServingClient(f"http://127.0.0.1:{ready['port']}").predict(
            gen.predict_rows(chunk, picked))
    finally:
        stop.set()
        thread.join(timeout=120)
    assert not thread.is_alive()
    assert res["snapshotStep"] == batches and res["servedRows"] == len(picked)
    # the champion is the tenant whose stamped online loss is lowest — under
    # a script key each tenant's loss is over ITS OWN rows, so the selector
    # compares populations, not models (PERF.md section 7)
    champion = result["stats"]["champion"]
    assert champion in range(4)
    assert [s["tenant"] for s in result["stats"]["shadows"]] == [0, 1, 2, 3]
    feats = linear_sgd.featurize(
        [chunk.text[i] for i in picked], chunk.followers[picked],
        chunk.favourites[picked], chunk.friends[picked],
        chunk.created_ms[picked], g["now_ms"], F_TEXT)
    want = learner.tenants[champion].predict(*feats)
    assert np.abs(np.asarray(res["predictions"]) - want).max() <= 1.0
