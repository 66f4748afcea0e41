"""The device stages of the train step carry stable names (PR 24):
``jax.named_scope``s in models/sgd.py and ops/{ragged,text_hash,gram}.py
that ``benchmark/stage_times.py`` sums a profile by. Lowering only — nothing
runs, so the tiny batch can keep hash2e18's 2^18 text dims."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from twtml_tpu.features.batch import (
    FeatureBatch,
    RaggedUnitBatch,
    UnitBatch,
    pack_batch,
)
from twtml_tpu.models.sgd import STAGE_SCOPES, make_sgd_train_step

F_TEXT = 1 << 18
# what a plane's branch of ``text_gram``'s switch holds, in order
GRAM_BRANCH_SCOPES = (
    "gram_count", "predict", "gram_matmul", "dual_loop", "writeback")


def _ragged(rows: int, row_len: int) -> RaggedUnitBatch:
    lens = np.full(rows, row_len // 2, np.int32)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    return RaggedUnitBatch(
        np.zeros(int(offsets[-1]), np.uint8), offsets,
        np.zeros((rows, 4), np.float32), np.zeros(rows, np.float32),
        np.ones(rows, np.float32), row_len,
    )


def _wire(form: str, rows: int, row_len: int):
    rb = _ragged(rows, row_len)
    if form == "packed":
        return pack_batch(rb)
    if form == "ragged":
        return rb
    if form == "units":
        return UnitBatch(
            np.zeros((rows, row_len), np.uint8), np.zeros(rows, np.int32),
            rb.numeric, rb.label, rb.mask,
        )
    return FeatureBatch(
        np.zeros((rows, row_len), np.int32),
        np.zeros((rows, row_len), np.float32), rb.numeric, rb.label, rb.mask,
    )


def _lowered(form: str, rows: int = 8, row_len: int = 16, **kw):
    step = make_sgd_train_step(
        num_text_features=F_TEXT, num_iterations=50, step_size=0.005,
        l2_reg=0.1, **kw,
    )
    weights = jnp.zeros(F_TEXT + 4, jnp.float32)
    return jax.jit(step, donate_argnums=0).lower(
        weights, _wire(form, rows, row_len))


def _op_names(lowered) -> set:
    """The op-name paths of the lowered module (its MLIR locations: what
    becomes each HLO instruction's ``op_name`` metadata)."""
    return set(re.findall(r'"(jit\(train_step\)[^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def op_names():
    return _op_names(_lowered("packed", quality=True))


@pytest.mark.parametrize("plane, branch", [
    ("exact", 0), ("bf16", 1), ("s8", 2),
])
def test_all_nine_stage_names_on_each_gram_plane(op_names, plane, branch):
    """Every scope name is on some operation of the step, and each of
    ``text_gram``'s three planes (the branches of its switch) runs the whole
    Gram basis on its own count matrix (PR 28): the build under
    ``gram_count``, ``u = C·w`` under ``predict``, the product under
    ``gram_matmul``, the loop under ``dual_loop`` and ``Cᵀα`` under
    ``writeback``. The planes share the names, the operand types tell them
    apart."""
    for scope in STAGE_SCOPES:
        assert any(f"/{scope}/" in n or n.endswith(f"/{scope}")
                   for n in op_names), scope
    inside = [n for n in op_names if f"/branch_{branch}_fun/" in n]
    assert inside, plane
    for scope in GRAM_BRANCH_SCOPES:
        assert any(f"/branch_{branch}_fun/{scope}/" in n for n in inside), (
            plane, scope)
    # nothing a branch runs is outside the five names
    assert all(any(f"/{scope}/" in n for scope in GRAM_BRANCH_SCOPES)
               for n in inside)


def test_no_scope_name_beyond_the_nine(op_names):
    """The reducer maps a path to the FIRST of the nine names on it; a
    tenth scope in the step would fall to ``other`` in silence."""
    known = set(STAGE_SCOPES)
    for name in op_names:
        for part in name.split("/")[1:-1]:
            if re.fullmatch(r"[a-z_]+", part) and part not in (
                "cond", "while", "body"
            ):
                assert part in known, name


@pytest.mark.parametrize("form, rows, row_len", [
    ("packed", 8, 16), ("packed", 16, 32), ("ragged", 8, 16),
    ("units", 8, 16), ("hashed", 8, 16),
])
def test_step_module_name_is_the_same_for_every_bucket_and_wire(
    form, rows, row_len
):
    """``jit_train_step`` whatever the bucket or the wire form: the device
    plane's ``XLA Modules`` line and the ``compile`` spans' ``fun`` name
    the step the same way in every cell."""
    text = _lowered(form, rows, row_len).as_text()
    assert re.search(r"^module @(\S+)", text, re.M).group(1) == "jit_train_step"


def compiled_funs(run):
    """The ``fun_name`` of every backend compilation (or persistent-cache
    fetch) ``run()`` causes — what the ``compile`` spans record."""
    import jax.monitoring as mon

    from twtml_tpu.telemetry.trace import BACKEND_COMPILE_EVENT

    seen = []

    def on_compile(event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            seen.append(str(kw.get("fun_name", "")))

    mon.register_event_duration_secs_listener(on_compile)
    try:
        run()
    finally:
        mon.unregister_event_duration_listener(on_compile)
    return seen


WIRES = [
    (form, rows, row_len)
    for form in ("packed", "ragged", "units", "hashed")
    for rows, row_len in ((8, 16), (16, 32))
]


@pytest.mark.parametrize("model_cls", ["linear", "logistic"])
def test_single_device_model_compiles_one_train_program(model_cls):
    """``jit(train_step)`` is the ONLY train program a single-device model
    ever compiles — once per (wire form, bucket), and nothing else that
    steps or scans the weights: the model has one step surface."""
    from twtml_tpu import models

    model = {
        "linear": models.StreamingLinearRegressionWithSGD,
        "logistic": models.StreamingLogisticRegressionWithSGD,
    }[model_cls](num_text_features=1 << 12, num_iterations=2)

    def run():
        for form, rows, row_len in WIRES:
            for _ in range(2):  # the repeat is served by jit's cache
                model.step(_wire(form, rows, row_len))

    funs = compiled_funs(run)
    train = [f for f in funs if "step" in f or "scan" in f]
    assert train == ["jit(train_step)"] * len(WIRES), funs
    assert [n for n in dir(model) if "many" in n or "scan" in n] == []


# ---------------------------------------------------------------------------
# PR 28: inside the Gram basis the step predicts and writes back through the
# plane's count matrix. What the PROGRAM asks for is in the lowered module
# (no gather from the [F] text weights, no scatter into them); what the
# COMPILER made of it — no f32 copy of a bf16 / s8 count matrix — only the
# TPU's compiler can say, so the step is compiled at hash2e18's / hash2e20's
# size for a described v5e (nothing runs; this file alone loads libtpu).

_GATHER = re.compile(r'stablehlo\.gather"[^\n]*? : \((tensor<[^>]*>)')
_SCATTER = re.compile(
    r'stablehlo\.scatter"[\s\S]*?\}\) : \([^)]*\) -> (tensor<[^>]*>)')


def gathers_and_scatters(lowered_text: str) -> tuple:
    """(operand type of every gather, result type of every scatter)."""
    return _GATHER.findall(lowered_text), _SCATTER.findall(lowered_text)


def test_gram_step_asks_for_no_gather_from_and_no_scatter_into_the_weights():
    weights = f"tensor<{F_TEXT}xf32>"
    gathers, scatters = gathers_and_scatters(
        _lowered("packed", quality=True).as_text())
    assert gathers and weights not in gathers  # the ragged wire's re-pad
    assert weights not in scatters
    # the same count finds both in the scatter loop: it can see them
    gathers, scatters = gathers_and_scatters(
        _lowered("packed", use_gram=False, quality=True).as_text())
    assert weights in gathers and weights in scatters


# stablehlo op counts of the two programs that stay OUTSIDE the Gram basis,
# as the parent of PR 28 lowered them (packed ragged wire, 8 rows of 16),
# plus what PR 33's re-pad of that wire added to both alike: 111 ops (the
# row gather's index arithmetic and the seven shifter stages), one of them
# a convert
_PARENT_OPS = {
    "serving": (254 + 111, {"gather": 4, "scatter": 2, "dot_general": 1,
                            "reduce": 12, "multiply": 14, "convert": 12}),
    "scatter_loop": (770 + 111, {"gather": 4, "scatter": 4, "dot_general": 3,
                                 "reduce": 63, "multiply": 44, "convert": 15,
                                 "while": 1}),
}


@pytest.mark.parametrize("program", sorted(_PARENT_OPS))
def test_programs_outside_the_gram_basis_are_the_parents(program):
    """``serving/engine.py`` pins ``use_gram=False`` and the scatter loop is
    the differential baseline: PR 28 moved the Gram step's predict into the
    switch and must not have touched either program."""
    if program == "serving":
        from twtml_tpu.serving.engine import PredictEngine

        model = PredictEngine(num_text_features=F_TEXT).model
        text = model._step.lower(
            model._weights, _wire("packed", 8, 16)).as_text()
    else:
        text = _lowered("packed", use_gram=False, quality=True).as_text()
    ops = re.findall(r"\bstablehlo\.([a-z_0-9]+)", text)
    total, some = _PARENT_OPS[program]
    assert len(ops) == total
    assert {name: ops.count(name) for name in some} == some


# ---------------------------------------------------------------------------
# PR 54: under a row panel (a mesh step) ``text_gram`` builds C as two arrays
# and ``CountPlane.gram`` concatenates two products; the ONE-DEVICE steps ask
# for no panel and lower to the parent's program.

_LOC = re.compile(r"^(#loc\d+) = loc\((.*)\)$", re.M)
_LOCATED_OP = re.compile(
    r"\bstablehlo\.([a-z_0-9]+)[^\n]*? -> (tensor<[^>]*>)[^\n]*?"
    r"loc\((#loc\d+)\)\s*$", re.M)


def scoped_ops(lowered, scopes, ops) -> list:
    """(scope, op, elements of its result) of every ``ops`` instruction of
    the lowered module whose location's op-name path holds one of
    ``scopes``."""
    text = lowered.as_text(debug_info=True)
    locs = dict(_LOC.findall(text))

    def path(ref: str) -> list:
        for _hop in range(8):  # a callsite / fused location names another
            body = locs.get(ref, "")
            named = re.match(r'"([^"]*)"', body)
            if named:
                return named.group(1).split("/")
            inner = re.findall(r"#loc\d+", body)
            if not inner:
                break
            ref = inner[0]
        return []

    out = []
    for op, result, ref in _LOCATED_OP.findall(text):
        if op in ops:
            size = int(np.prod([int(d) for d in re.findall(r"(\d+)x", result)]))
            under = path(ref)
            out += [(s, op, size) for s in scopes if s in under]
    return out


def _one_device_programs():
    from twtml_tpu.parallel import TenantStackModel

    m, rows, row_len = 4, 8, 16

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    def stacked(model, step, wire):
        return jax.jit(step, donate_argnums=0).lower(
            shape(m, F_TEXT + 4), {k: shape(m) for k in model._hyper}, wire)

    def arms():
        model = TenantStackModel(
            m, num_text_features=F_TEXT, tenant_key="all",
            step_sizes=[0.005, 0.005, 0.0025, 0.0025],
            l2_regs=[0.1, 0.01, 0.1, 0.01], quality=True)
        return stacked(model, model._shared, _ragged(rows, row_len))

    def tenants():
        model = TenantStackModel(m, num_text_features=F_TEXT, l2_reg=0.1,
                                 step_size=0.005, quality=True)
        return stacked(model, model._mapped, RaggedUnitBatch(
            shape(m, 64, dtype=jnp.uint16),
            shape(m, rows + 1, dtype=jnp.int32), shape(m, rows, 4),
            shape(m, rows), shape(m, rows), row_len=row_len))

    return {"single": lambda: _lowered("packed", quality=True),
            "arms": arms, "tenants": tenants}


# (scope, op) → how many the parent of PR 54 lowered, 8 rows of 16, all three
# planes' branches together (the concatenates are rung 2's run starts and
# the ``[F + 4]`` weights; the arms' one dynamic slice takes an arm's row of
# the ``[M, 4]`` numeric weights)
_SINGLE_MODEL_OPS = {
    ("gram_count", "concatenate"): 2, ("gram_count", "dot_general"): 2,
    ("gram_matmul", "dot_general"): 6, ("predict", "dot_general"): 3,
    ("writeback", "concatenate"): 3, ("writeback", "dot_general"): 3,
}
_PARENT_GRAM_OPS = {
    "single": _SINGLE_MODEL_OPS,
    "tenants": _SINGLE_MODEL_OPS,
    "arms": {
        ("gram_count", "concatenate"): 2, ("gram_count", "dot_general"): 2,
        ("gram_matmul", "dot_general"): 6,
        ("predict", "concatenate"): 6, ("predict", "dot_general"): 12,
        ("predict", "dynamic_slice"): 1,
        ("writeback", "concatenate"): 9, ("writeback", "dot_general"): 12,
    },
}


@pytest.mark.parametrize("program", sorted(_PARENT_GRAM_OPS))
def test_one_device_gram_scopes_lower_to_the_parents_program(program):
    """The six one-chip cells call ``text_gram`` with ``rows = 0``: one
    build, one G product a plane, and NOTHING of C's size sliced or
    concatenated — the products, dynamic slices and concatenates under the
    four scopes that touch C are the parent's, count for count (their
    whole StableHLO text was the parent's character for character when
    PR 54 was built: PERF.md §6)."""
    found = scoped_ops(
        _one_device_programs()[program](),
        ("gram_count", "gram_matmul", "writeback", "predict"),
        ("dot_general", "dynamic_slice", "concatenate"))
    counts = collections.Counter((scope, op) for scope, op, _size in found)
    assert dict(counts) == _PARENT_GRAM_OPS[program]
    assert not [(scope, op) for scope, op, _size in found
                if scope.startswith("gram") and op == "dynamic_slice"]
    # no slice or concatenate as large as C (8 rows of it, on any plane)
    moved = [(scope, op, size) for scope, op, size in found
             if op != "dot_general" and size >= 8 * F_TEXT]
    assert not moved, moved


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


# Since PR 33 the TPU-compiled steps below are compiled for the RAGGED wire,
# the wire every cell ships; until then they were compiled for the padded
# ``UnitBatch``. The padded wire's COMPILED steps (``--wire padded``, run in
# no cell) are therefore no longer checked here: everything after the
# ``repad`` scope is the same traced program for both wires, and the padded
# form is still lowered by the module-name and program-count tests above.
ROWS = 2048
ROW_LEN = 512
UNITS = 307200  # the 280-unit cells' commonest units bucket


def _ragged_shapes(shape, shards: int = 1) -> RaggedUnitBatch:
    """The cells' wire as shapes: ROWS rows of the ragged units wire
    (uint16: 30% of the mixes' tweets are not ASCII), shard-aligned over
    ``shards`` data shards; ``shape(dims, dtype, *spec)`` places a leaf
    whose leading dim the data axis shards."""
    return RaggedUnitBatch(
        shape((UNITS,), jnp.uint16, "data"),
        shape((ROWS + shards,), jnp.int32, "data"),
        shape((ROWS, 4), jnp.float32, "data", None),
        shape((ROWS,), jnp.float32, "data"),
        shape((ROWS,), jnp.float32, "data"),
        row_len=ROW_LEN, num_shards=shards)


def _compile_single(topo) -> str:
    from jax.sharding import SingleDeviceSharding

    dev = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype, *_spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    step = make_sgd_train_step(
        num_text_features=F_TEXT, num_iterations=50, step_size=0.005,
        l2_reg=0.1, quality=True)
    return jax.jit(step, donate_argnums=0).lower(
        shape((F_TEXT + 4,), jnp.float32), _ragged_shapes(shape),
    ).compile().as_text()


def _compile_mesh(topo, mesh_shape, axes, body, w_spec, weights_of) -> str:
    """The mesh step ``body`` under ``shard_map`` on the described chips,
    compiled for ROWS rows of the shard-aligned ragged wire at row length
    512."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from twtml_tpu.models.base import StepOutput

    mesh = Mesh(np.array(topo.devices).reshape(mesh_shape), axes)

    def shape(dims, dtype, *spec):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=NamedSharding(mesh, P(*spec)))

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(w_spec, P("data")),
        out_specs=(w_spec, StepOutput(
            predictions=P("data"), count=P(), mse=P(), real_stdev=P(),
            pred_stdev=P(), quality=P())),
    ), donate_argnums=0)
    batch = _ragged_shapes(shape, shards=mesh.shape["data"])
    return step.lower(weights_of(shape), batch).compile().as_text()


def _compile_2x2(topo) -> str:
    from jax.sharding import PartitionSpec as P

    from twtml_tpu.parallel.sharding import _make_feature_sharded_step

    f_text = 1 << 20  # hash2e20: a 2^19 slice a chip
    body = _make_feature_sharded_step(
        f_text=f_text, f_text_local=f_text // 2, num_iterations=50,
        step_size=0.005, mini_batch_fraction=1.0, l2_reg=0.1,
        convergence_tol=0.001, residual_fn=None, prediction_fn=None,
        round_predictions=True, data_axis="data", model_axis="model",
        quality=True)
    return _compile_mesh(
        topo, (2, 2), ("data", "model"), body,
        {"text": P("model"), "num": P()},
        lambda shape: {"text": shape((f_text,), jnp.float32, "model"),
                       "num": shape((4,), jnp.float32)})


def _compile_4x1(topo) -> str:
    """The data-only mesh step (models/sgd.py under ``axis_name``) at
    hash2e18's size: replicated weights, a quarter of the rows a chip."""
    from jax.sharding import PartitionSpec as P

    body = make_sgd_train_step(
        num_text_features=F_TEXT, num_iterations=50, step_size=0.005,
        l2_reg=0.1, quality=True, axis_name="data")
    return _compile_mesh(
        topo, (4,), ("data",), body, P(),
        lambda shape: shape((F_TEXT + 4,), jnp.float32))


# layout → (compile, width of the [F] weights a chip holds, rows × width
# of the smallest count-matrix panel a contraction reads)
_TPU_STEPS = {
    "single": (_compile_single, F_TEXT, ROWS * F_TEXT),
    "2x2": (_compile_2x2, 1 << 19, (ROWS // 2) << 19),
    "4x1": (_compile_4x1, F_TEXT, (ROWS // 4) * F_TEXT),
}


@pytest.fixture(scope="module")
def tpu_steps(topo):
    """layout → the text of the step the TPU's compiler made."""
    cache = {}

    def of(layout: str) -> str:
        if layout not in cache:
            cache[layout] = _TPU_STEPS[layout][0](topo)
        return cache[layout]

    return of


@pytest.fixture(scope="module")
def tpu_branches(tpu_steps):
    """layout → the three branches of the compiled step's plane switch."""
    cache = {}

    def of(layout: str) -> list:
        if layout not in cache:
            cache[layout] = plane_branches(tpu_steps(layout))
        return cache[layout]

    return of


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_CALLED = re.compile(
    r"(?:to_apply|body|condition|calls|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
# result type (a tuple's has spaces) and op of an instruction line
_RESULT = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z\-]*)\(")
_ARRAY = re.compile(r"\b(f32|bf16|s8|s32|u8|pred)\[([0-9,]*)\]")


def plane_branches(hlo: str) -> list:
    """The compiled module's plane switch (its one ``conditional`` of three
    branches) as ``[{"top": [...], "all": [...]}, ...]``: per branch the
    instruction lines it runs — ``top`` without the insides of fusions (a
    value inside a fusion lives in registers; a fusion's RESULT is an array
    in memory), ``all`` with them."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name and line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)

    def walk(start: str, into_fusions: bool) -> list:
        seen, order, lines = {start}, [start], []
        while order:
            for line in comps[order.pop()]:
                lines.append(line)
                op = _RESULT.match(line)
                if op and op.group(2) == "fusion" and not into_fusions:
                    continue
                for one, many in _CALLED.findall(line):
                    for callee in [one] if one else re.findall(
                            r"%([\w.\-]+)", many):
                        if callee in comps and callee not in seen:
                            seen.add(callee)
                            order.append(callee)
        return lines

    switches = [re.findall(r"%([\w.\-]+)", m) for line in hlo.splitlines()
                for m in re.findall(r"branch_computations=\{([^}]*)\}", line)]
    (branches,) = [b for b in switches if len(b) == 3]
    return [{"top": walk(b, False), "all": walk(b, True)} for b in branches]


def _results(lines) -> list:
    """(op, dtype, element count) of every array an instruction yields."""
    out = []
    for line in lines:
        m = _RESULT.match(line)
        if m:
            for dtype, dims in _ARRAY.findall(m.group(1)):
                size = int(np.prod([int(d) for d in dims.split(",") if d]))
                out.append((m.group(2), dtype, size))
    return out


@pytest.mark.parametrize("layout", sorted(_TPU_STEPS))
@pytest.mark.parametrize("plane, branch", [
    ("exact", 0), ("bf16", 1), ("s8", 2),
])
def test_compiled_gram_branch_reads_only_its_count_matrix(
    tpu_branches, layout, plane, branch
):
    """Counted on the program the TPU's compiler made, per plane: no gather
    whose operand is the ``[F]`` text weights, no scatter whose result is;
    the plane's own count matrix is there in its own type; and on the bf16
    and s8 planes no f32 array as large as the panel the contractions read
    is ever in memory (an f32 copy of C would cost more than the gather it
    replaced: 2 GiB written and read back)."""
    _compile, width, panel = _TPU_STEPS[layout]
    took = tpu_branches(layout)[branch]
    for line in took["all"]:
        m = _RESULT.match(line)
        if m and m.group(2) == "gather":
            assert f"f32[{width}]" not in line.split("gather(")[1], line
        if m and m.group(2) == "scatter":
            assert not m.group(1).startswith(f"f32[{width}]"), line
    arrays = _results(took["top"])
    own = {"exact": "f32", "bf16": "bf16", "s8": "s8"}[plane]
    assert any(d == own and n >= panel for _op, d, n in arrays), plane
    if plane != "exact":
        wide = [(op, d, n) for op, d, n in arrays if d == "f32" and n >= panel]
        assert not wide, wide


# instructions that name an array another instruction wrote
_ALIASES = ("get-tuple-element", "bitcast", "tuple", "parameter")


@pytest.mark.parametrize("layout", sorted(_TPU_STEPS))
@pytest.mark.parametrize("plane, branch", [
    ("exact", 0), ("bf16", 1), ("s8", 2),
])
def test_compiled_fast_plane_writes_its_count_matrix_once(
    tpu_branches, layout, plane, branch
):
    """PR 30: C keeps the ``[B, k_hi, k_lo]`` its build writes, so on the
    program the TPU's compiler made a fast plane's branch holds ONE array
    of C's size in the plane's type — the build's, never a layout copy or
    a second materialisation (at hash2e20 the ``reshape`` to ``[B, F]``
    was a ``copy`` of 2 GiB a batch) — and the fusion that writes it also
    yields the ``f32[ROWS]`` ``u = C·w``: the predict contraction in the
    build's epilogue, no read of C of its own.

    PR 54, on a mesh (``2x2``: the feature-sharded step; ``4x1``: the
    data-only one): C is BUILT as two arrays, this shard's rows and the
    rest (ops/gram.text_gram), each written by the one-hot product's
    fusion, the own rows' one with its ``f32[rows]`` ``u``; together they
    are C's bytes, and NO further array of the plane's type as large as
    the row panel is written — the panel the G product and the write-back
    read IS the own rows' build (until then a ``dynamic-slice`` fusion
    wrote it a second time: 1 GiB read + 1 GiB written a batch at
    hash2e20). The exact plane builds the same two arrays by its scatter
    (no epilogue there: its ``u`` is a reduction of its own)."""
    _compile, width, panel = _TPU_STEPS[layout]
    own = panel // width  # this shard's rows: all of them on one device
    own_type = {"exact": "f32"}.get(plane, plane)
    # (line, the arrays it yields) of every instruction that WRITES memory
    written = [(line, _results([line]))
               for line in tpu_branches(layout)[branch]["top"]]
    written = [(line, arrays) for line, arrays in written
               if arrays and arrays[0][0] not in _ALIASES]
    # every array of the plane's type of the panel's size or more (the
    # exact plane's scatter yields a flat array that a ``reshape`` names
    # again in C's shape: one array)
    held = [(line, arrays, n) for line, arrays in written
            for op, d, n in arrays
            if d == own_type and n >= panel and op != "reshape"]
    builds = [own * width] + ([(ROWS - own) * width] if own < ROWS else [])
    assert sorted(n for _line, _arrays, n in held) == sorted(builds), [
        line[:200] for line, _arrays, _n in held]
    assert sum(builds) == ROWS * width  # together: C's bytes, once
    for line, arrays, _n in held:
        assert arrays[0][0] == "fusion", line[:200]  # no slice, no copy
        if plane != "exact":
            assert "gram_count/dot_general" in line, line[:300]
    if plane != "exact":
        # u rides the own rows' build, and that build alone
        (with_u,) = [n for _line, arrays, n in held
                     if ("fusion", "f32", own) in arrays]
        assert with_u == own * width


# ---------------------------------------------------------------------------
# PR 33: the ragged wire is re-padded by whole 128-lane rows of the units
# buffer and a barrel shifter (ops/ragged.py), never one gather an element.

_SLICE_SIZES = re.compile(r"slice_sizes=\{([0-9,]*)\}")


@pytest.mark.parametrize("layout", sorted(_TPU_STEPS))
def test_compiled_repad_gathers_lane_rows_not_elements(tpu_steps, layout):
    """Counted on the program the TPU's compiler made for the cells' wire
    (``jit_train_step``, the feature-sharded ``2x2`` and the data-only
    ``4x1`` mesh steps): every gather under the ``repad`` scope takes
    slices of 128 units, and together they take at most
    ``rows a chip x (ceil(L / 128) + 1)`` of them — the ``[B, L]`` gather
    of one-element slices (1,048,576 at this size) cannot come back
    unseen."""
    data_shards = {"single": 1, "2x2": 2, "4x1": 4}[layout]
    slices = 0
    for line in tpu_steps(layout).splitlines():
        m = _RESULT.match(line)
        if not (m and m.group(2) == "gather" and "/repad/" in line):
            continue
        sizes = [int(n) for n in _SLICE_SIZES.search(line).group(1).split(",")]
        assert int(np.prod(sizes)) == 128, line
        ((_dtype, dims),) = _ARRAY.findall(m.group(1))
        slices += int(np.prod([int(d) for d in dims.split(",")])) // 128
    assert 0 < slices <= ROWS // data_shards * (-(-ROW_LEN // 128) + 1)


# ---------------------------------------------------------------------------
# PR 36: the tenant plane's mapped program is sized by the tenant wire's row
# rung (features/batch.tenant_row_rungs), not by the whole batch.

def test_compiled_tenant_program_is_sized_by_the_rung(topo):
    """The mapped program the TPU's compiler makes for the tenant cell's
    wire — four parts of the first rung of 2,048 rows, 640, with the rung's
    98,304-unit buffer: its Gram product is ``[640, 640]``, nothing in it
    has the whole batch's 2,048 rows, and its temporaries stay under a
    third of the 4,318,823,936 B the ``[4, 2048]`` program takes (PERF.md
    §4; that one is not compiled here)."""
    from jax.sharding import SingleDeviceSharding

    from twtml_tpu.features.batch import tenant_row_rungs
    from twtml_tpu.parallel import TenantStackModel

    m, rung = 4, tenant_row_rungs(ROWS, 4)[0]
    assert rung == 640
    dev = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    model = TenantStackModel(m, num_text_features=F_TEXT, l2_reg=0.1,
                             step_size=0.005, quality=True)
    wire = RaggedUnitBatch(
        shape(m, 98304, dtype=jnp.uint16), shape(m, rung + 1, dtype=jnp.int32),
        shape(m, rung, 4), shape(m, rung), shape(m, rung), row_len=ROW_LEN)
    compiled = jax.jit(model._mapped, donate_argnums=0).lower(
        shape(m, F_TEXT + 4), {k: shape(m) for k in model._hyper}, wire,
    ).compile()
    text = compiled.as_text()
    assert f"f32[{rung},{rung}" in text
    assert f"[{ROWS}," not in text and f",{ROWS}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4_318_823_936 // 3


def test_compiled_tenant_program_at_the_top_rung_is_the_whole_batchs(topo):
    """The mapped program of a LOPSIDED split (one tenant with more than
    62.5% of a batch, as ``--tenantKey lang`` gives on a mostly-ASCII
    stream): four parts of the TOP rung, the batch's own 2,048 rows, each
    with the parent's whole units buffer. Its Gram product is
    ``[2048, 2048]`` and it reserves ONE tenant's temporaries at a time,
    4.0 GiB (measured on the chip in PR 42: PERF.md section 6). A shape per
    tenant (ROADMAP R1 (f)) changes this figure."""
    from jax.sharding import SingleDeviceSharding

    from twtml_tpu.features.batch import tenant_row_rungs
    from twtml_tpu.parallel import TenantStackModel

    m, rung = 4, tenant_row_rungs(ROWS, 4)[-1]
    assert rung == ROWS
    dev = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    model = TenantStackModel(m, num_text_features=F_TEXT, l2_reg=0.1,
                             step_size=0.005, quality=True, tenant_key="lang")
    wire = RaggedUnitBatch(
        shape(m, UNITS, dtype=jnp.uint16), shape(m, rung + 1, dtype=jnp.int32),
        shape(m, rung, 4), shape(m, rung), shape(m, rung), row_len=ROW_LEN)
    compiled = jax.jit(model._mapped, donate_argnums=0).lower(
        shape(m, F_TEXT + 4), {k: shape(m) for k in model._hyper}, wire,
    ).compile()
    assert f"f32[{ROWS},{ROWS}" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 4 * 2**30 <= temp < 5 * 2**30      # 4,318,823,936 B as compiled


def test_compiled_two_rung_program_reserves_one_top_rung_step(topo):
    """PR 49: the ONE program of a lopsided split — the fullest tenant's
    step at the top rung (2,048 rows, ``_two_rung_units``' 327,680 units) and
    the ``lax.map`` of the step over the three others at the first rung (640
    rows, that rung's 102,400 units) — as the TPU's compiler makes it for
    ``hash2e18-lang4-trimmed-280``'s wire. Both Gram products are in it, and
    ``tenant_map`` with the stage scopes around BOTH halves (the fullest's
    ops directly under the scope, the others' under its ``while``). The
    halves run in turn, so it reserves the one-tenant top-rung step's
    temporaries — under the ``[4, 2048]`` map's 4,318,823,936 B, which also
    held one tenant at a time — and not that plus the 640-rung map's
    (~1.36e9 B, ``test_compiled_tenant_program_is_sized_by_the_rung``)."""
    from jax.sharding import SingleDeviceSharding

    from twtml_tpu.features.batch import TwoRungWire, tenant_row_rungs
    from twtml_tpu.parallel import TenantStackModel

    m = 4
    low, _mid, top = tenant_row_rungs(ROWS, m)
    assert (low, top) == (640, ROWS)
    dev = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    def member(k, rung, units):
        return RaggedUnitBatch(
            shape(k, units, dtype=jnp.uint16),
            shape(k, rung + 1, dtype=jnp.int32), shape(k, rung, 4),
            shape(k, rung), shape(k, rung), row_len=ROW_LEN)

    model = TenantStackModel(m, num_text_features=F_TEXT, l2_reg=0.1,
                             step_size=0.005, quality=True, tenant_key="lang")
    # the 280-unit mix's buckets (303,104 … 315,392 units) all ship as this
    wire = TwoRungWire(member(1, top, 327680), member(m - 1, low, 102400),
                       shape(m, dtype=jnp.int32))
    compiled = jax.jit(model._mapped, donate_argnums=0).lower(
        shape(m, F_TEXT + 4), {k: shape(m) for k in model._hyper}, wire,
    ).compile()
    text = compiled.as_text()
    assert f"f32[{top},{top}" in text and f"f32[{low},{low}" in text
    names = set(re.findall(r'op_name="([^"]*)"', text))
    mapped = {n for n in names
              if n.startswith("jit(_mapped)/tenant_map/while/body/")}
    direct = {n for n in names
              if n.startswith("jit(_mapped)/tenant_map/") and n not in mapped}
    for scope in (s for s in STAGE_SCOPES if s != "unpack"):
        assert any(f"/{scope}/" in n for n in mapped), scope
        assert any(f"/{scope}/" in n for n in direct), scope
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 4 * 2**30 <= temp <= 4_318_823_936     # 4,313,642,496 B as compiled


# ---------------------------------------------------------------------------
# PR 47: ``--tenantKey all`` — M arms on the SAME rows share the count matrix
# and G. PR 50: they share every READ of it too — ``u = C·[w_1…w_M]`` and
# ``Cᵀ·[α_1…α_M]`` are one pass each, outside the map; only the dual loop
# is mapped.

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\(([^)]*)\)")


def _touching(lines, least: int) -> tuple:
    """``(writers, readers, loops)`` among the instructions of ``lines``
    (a branch's ``top``: fusions counted as one operation each): those
    that WRITE an array of at least ``least`` elements, those that take
    one as an operand, and the ``while``s that carry one. An instruction
    that only names another's array (``_ALIASES``) is neither."""
    big, writers, readers, loops = set(), [], [], []
    for line in lines:
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, result, op, operands = m.groups()
        holds = any(n >= least for _op, _d, n in _results([line]))
        if holds:
            big.add(name)
        if op == "while":
            if holds:
                loops.append(line)
            continue
        if op in _ALIASES:
            continue
        if holds:
            writers.append(line)
        if any(o in big for o in re.findall(r"%([\w.\-]+)", operands)):
            readers.append(line)
    return writers, readers, loops


def test_compiled_arms_program_builds_c_and_g_once_and_maps_the_rest(topo):
    """The program the TPU's compiler makes for the cell
    ``hash2e18-grid4-trimmed-280`` (four arms, 2,048 rows, the cells' wire).
    In every plane's branch ONE count matrix of the plane's type and ONE
    ``[2048, 2048]`` Gram product, both outside the map's ``while``; under
    ``/arm_map/while/body/`` the ``dual_loop`` and NO ``predict``,
    ``writeback`` or ``gram_*``, and no array of C's size carried into any
    loop: C is read OUTSIDE loops only, by at most THREE operations of its
    size — on the fast planes two, the G product and the ONE write-back
    pass for all four arms, because the fusion that WRITES C also yields
    the four ``f32[2048]`` ``u_m = C·w_m`` (the predict contraction of every
    arm in the build's epilogue: no read of C, as in the single model's
    step); on the exact plane, whose build is a scatter, one pass more for
    the four ``u_m`` together. No array of C's size is written but C. No
    op-name holds both ``arm_map`` and ``predict`` / ``writeback``. And the
    whole of it reserves the single model's temporaries (4,308,146,176 B as
    compiled) and not M times them."""
    from jax.sharding import SingleDeviceSharding

    from twtml_tpu.parallel import TenantStackModel

    m = 4
    dev = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype, *_spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    model = TenantStackModel(
        m, num_text_features=F_TEXT, tenant_key="all",
        step_sizes=[0.005, 0.005, 0.0025, 0.0025],
        l2_regs=[0.1, 0.01, 0.1, 0.01], quality=True)
    compiled = jax.jit(model._shared, donate_argnums=0).lower(
        shape((m, F_TEXT + 4), jnp.float32),
        {k: shape((m,), jnp.float32) for k in model._hyper},
        _ragged_shapes(shape),
    ).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 4 * 2**30 <= temp < 4.1 * 2**30     # 4,307,057,664 B as compiled
    text = compiled.as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    both = [n for n in names if "arm_map" in n.split("/")
            and {"predict", "writeback"} & set(n.split("/"))]
    assert not both, both
    full = ROWS * F_TEXT
    for plane, took in zip(("f32", "bf16", "s8"), plane_branches(text)):
        mapped = [line for line in took["all"]
                  if "/arm_map/while/body/" in line]
        once = [line for line in took["all"] if "/arm_map/" not in line]
        grams = [line for line in once if "/gram_matmul/" in line
                 and re.search(r" convolution\(", line)
                 and f"f32[{ROWS},{ROWS}]" in line]
        assert grams, plane
        assert any("/dual_loop/" in line for line in mapped), plane
        assert not any(f"/{scope}/" in line for line in mapped for scope in (
            "predict", "writeback", "gram_matmul", "gram_count")), plane
        assert any("/predict/" in line for line in once), plane
        assert any("/writeback/" in line for line in once), plane
        writers, readers, loops = _touching(took["top"], full)
        assert not loops, (plane, loops)
        assert not any("/arm_map/" in line for line in writers + readers)
        if plane == "f32":
            # the scatter build (its flat result and the ``[B, F]`` view),
            # then u for all arms, G, the write-back: one pass each
            stages = [re.search(r"branch_0_fun/(\w+)/", line).group(1)
                      for line in readers if "op_name" in line]
            assert sorted(s for s in stages if s != "gram_count") == [
                "gram_matmul", "predict", "writeback"], (plane, readers)
            continue
        (build,) = writers
        assert "/gram_count/" in build, plane
        assert _results([build]).count(("fusion", "f32", ROWS)) == m, build
        assert len(readers) == 2, (plane, readers)
        assert sum("/gram_matmul/" in line for line in readers) == 1
        assert sum("/writeback/" in line for line in readers) == 1


# ---------------------------------------------------------------------------
# PR 52: the arms on the 2 x 2 mesh (``--tenantKey all --modelShards 2``,
# configuration ``hash2e20-grid4``): ``hash2e20``'s sharded C, G panel and
# collectives ONCE a batch, shared by four models.

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (.*?) (all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\([^\n]*op_name=\"([^\"]*)\"",
    re.M)


def _compile_2x2_arms(topo):
    """The feature-sharded step with ``arms`` at hash2e20's width, four
    recipes, the cells' wire: ``(compiled text, temporaries in bytes)``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from twtml_tpu.models.base import StepOutput
    from twtml_tpu.parallel.sharding import _make_feature_sharded_step

    m, f_text = 4, 1 << 20
    body = _make_feature_sharded_step(
        f_text=f_text, f_text_local=f_text // 2, num_iterations=50,
        step_size=np.asarray([0.005, 0.005, 0.0025, 0.0025], np.float32),
        l2_reg=np.asarray([0.1, 0.01, 0.1, 0.01], np.float32),
        mini_batch_fraction=1.0, convergence_tol=0.001, residual_fn=None,
        prediction_fn=None, round_predictions=True, data_axis="data",
        model_axis="model", quality=True, arms=True)
    mesh = Mesh(np.array(topo.devices).reshape((2, 2)), ("data", "model"))

    def shape(dims, dtype, *spec):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=NamedSharding(mesh, P(*spec)))

    w_spec = {"text": P(None, "model"), "num": P()}
    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(w_spec, P("data")),
        out_specs=(w_spec, StepOutput(
            predictions=P(None, "data"), count=P(), mse=P(), real_stdev=P(),
            pred_stdev=P(), quality=P())),
    ), donate_argnums=0)
    compiled = step.lower(
        {"text": shape((m, f_text), jnp.float32, None, "model"),
         "num": shape((m, 4), jnp.float32)},
        _ragged_shapes(shape, shards=2)).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def test_compiled_mesh_arms_program_is_hash2e20s_with_m_payloads(
        topo, tpu_steps):
    """The program the TPU's compiler makes for the cell
    ``hash2e20-grid4-trimmed-280`` (four arms on the 2 x 2 mesh, 2,048 rows,
    a 2^19 slice a chip) beside ``hash2e20``'s own (the ``2x2`` case).

    The SAME NUMBER of collective instructions as that program, every one
    under the ``collective`` scope inside a stage, NONE inside the map's
    ``while``, the per-arm ones with ``[M, ·]`` payloads: the ``u``
    partials' psum ``[4, 1024]`` and all-gather ``[4, 2048]`` under
    ``predict``, the write-back deltas ``[4, 524288]`` and ``[4, 4]`` in
    one all-reduce under ``writeback``. ``arm_map`` holds the ``dual_loop``
    (under its ``while``) and no ``predict`` / ``writeback`` / ``gram_*``.
    In each fast plane's branch C is WRITTEN once, as TWO arrays (PR 54:
    this shard's rows and the rest), the own rows' by the fusion that also
    yields the four ``f32[1024]`` ``u_m = rows(C)·w_m`` (no read of C for
    any arm's predict) and NO third array of the panel's size; the own
    rows' array is READ by three operations — the two G products (against
    itself, against the rest) and the ONE write-back pass, which yields
    all four ``[524288]`` deltas — and the rest's by one; nothing of the
    panel's size is carried into a loop. And the whole of it reserves
    ``hash2e20``'s temporaries (6,465,938,432 B as compiled: the three
    planes' count matrices, once; 8,621,713,920 B while the row panel was
    a second array) and not M times them."""
    m, width = 4, 1 << 19
    text, temp = _compile_2x2_arms(topo)
    assert 6 * 2**30 <= temp < 6.1 * 2**30
    mine, single = _COLLECTIVE.findall(text), _COLLECTIVE.findall(
        tpu_steps("2x2"))
    assert len(mine) == len(single) >= 20, (len(mine), len(single))
    for _result, kind, path in mine:
        parts = path.split("/")
        assert "collective" in parts, (kind, path)
        assert "/arm_map/while/" not in path, (kind, path)
        assert any(s in parts for s in STAGE_SCOPES), (kind, path)

    def carried(kind, stage, array):
        return [r for r, k, p in mine if k == kind and f"/{stage}/" in p
                and array in r]

    assert carried("all-reduce", "predict", f"f32[{m},{ROWS // 2}]")
    assert carried("all-gather", "predict", f"f32[{m},{ROWS}]")
    deltas = carried("all-reduce", "writeback", f"f32[{m},{width}]")
    assert deltas and all(f"f32[{m},4]" in r for r in deltas)
    assert not carried("all-reduce", "writeback", f"f32[{width}]")

    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert not [n for n in names if "vmap" in n]
    both = [n for n in names if "arm_map" in n.split("/")
            and {"predict", "writeback"} & set(n.split("/"))]
    assert not both, both
    own, panel = ROWS // 2, ROWS // 2 * width
    for plane, took in zip(("f32", "bf16", "s8"), plane_branches(text)):
        mapped = [line for line in took["all"]
                  if "/arm_map/while/body/" in line]
        once = [line for line in took["all"] if "/arm_map/" not in line]
        assert any("/dual_loop/" in line for line in mapped), plane
        assert not any(f"/{scope}/" in line for line in mapped for scope in (
            "predict", "writeback", "gram_matmul", "gram_count")), plane
        grams = [line for line in once if "/gram_matmul/" in line
                 and re.search(r" convolution\(", line)
                 and f"[{own},{own}" in line]
        assert len(grams) == 2, (plane, grams)
        writers, readers, loops = _touching(took["top"], panel)
        assert not loops, (plane, loops)
        assert not any("/arm_map/" in line for line in writers + readers)
        if plane == "f32":
            continue   # the scatter build: its passes are the one-device's
        assert len(writers) == 2, (plane, writers)  # the two builds
        assert all("/gram_count/" in line for line in writers), plane
        assert sorted(_results([line]).count(("fusion", "f32", own))
                      for line in writers) == [0, m], writers
        stages = sorted(
            re.search(r"branch_\d_fun/(\w+)/", line).group(1)
            for line in readers)
        assert stages == ["gram_matmul", "gram_matmul", "writeback"], (
            plane, readers)
        (back,) = [line for line in readers if "/writeback/" in line]
        assert _results([back]).count(("fusion", "f32", width)) == m, back


# ---------------------------------------------------------------------------
# PR 55: the primal learner (``l1_reg``: MLlib's L1Updater), whose 50
# iterations read the count matrix — compiled for the chip at the cell's
# size, so that what the compiler refuses (a tiling, the kernel's VMEM) or
# adds (a copy of C in front of the kernel) costs no chip time to find.

def test_compiled_primal_step_reads_c_through_the_kernel_alone(topo):
    """``lasso2e18``'s step on one v5e chip: on the bf16 and the s8 plane
    the loop's body calls the Pallas pass (ONE ``tpu_custom_call`` a plane,
    inside the ``while`` under ``primal_loop``, taking C as the count build
    wrote it: no copy, no convert, no transpose of an array of C's size
    anywhere in the program), the exact plane's two fusions instead; no G
    product and nothing of the dual basis; ``u = C·w`` for the pre-update
    margin still rides the count build's epilogue (the build's fusion is
    the one writer of C and has the ``[ROWS]`` f32 result beside it)."""
    from jax.sharding import SingleDeviceSharding

    dev = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype, *_spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    step = make_sgd_train_step(
        num_text_features=F_TEXT, num_iterations=50, step_size=0.005,
        l1_reg=0.1, quality=True)
    compiled = jax.jit(step, donate_argnums=0).lower(
        shape((F_TEXT + 4,), jnp.float32), _ragged_shapes(shape)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for line, plane in zip(sorted(calls, key=lambda ln: "s8[" in ln),
                           ("bf16", "s8")):
        assert f"{plane}[{ROWS},512,512]" in line
        assert "/primal_loop/while/body/" in line and "/primal_pass/" in line
    names = set(re.findall(r'op_name="([^"]*)"', text))
    parts = {p for n in names for p in n.split("/")}
    assert not {"gram_matmul", "dual_loop", "writeback"} & parts
    assert not [line for line in text.splitlines() if re.search(
        rf"= (bf16|s8)\[{ROWS},512,512\]\S* (copy|transpose)\(", line)]
    builds = [line for line in text.splitlines() if " fusion(" in line
              and re.search(rf"\(f32\[{ROWS}\]\S*, (bf16|s8)\[{ROWS},512,512\]",
                            line)]
    assert len(builds) == 2 and all("/gram_count/" in b for b in builds)
    # the three planes' count matrices as hash2e18 reserves them, less G
    assert 3.5 * 2**30 < compiled.memory_analysis().temp_size_in_bytes < (
        4.2 * 2**30)
