"""PR 54: under a row panel (every mesh step) ``ops/gram.text_gram`` BUILDS the
count matrix as two arrays — the caller's own rows and the rest — and
``CountPlane`` contracts the own rows' array as built, where PRs 28–53 built
ONE C and sliced the caller's rows out of it (``left(C)·Cᵀ``: on the TPU a
second array of the panel's size written every batch, PERF.md §6). The
sliced form is kept HERE as the plain reference: the G panel, ``u``, the
write-back delta for every ``data`` index, and the new weights and outputs
of the 2 x 2 step, the 4 x 1 data-only step and the 2 x 2 arms step, bit
for bit on the bf16 and s8 planes (every entry of G is the same integer
sum; ``u`` and the delta are the same reductions of the same rows) and to
float32 rounding on the exact plane (an f32 product of fractions, whose
blocking may differ with the operand's rows). Virtual CPU devices, small
sizes.

Two data shards cannot tell a roll by ``−row_start`` from one by
``+row_start`` (both are half the batch); four can, and there ``B − rows``
is three times ``rows``: the 4 x 1 cases are the ones a wrong direction or
a rest taken for another panel fails. Every row of every batch differs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from test_tenant_grid import L2S, STEPS
from test_tenant_grid_mesh import F_TEXT, PLANES, ROWS, _stream_for
from twtml_tpu.models import sgd as sgd_module
from twtml_tpu.ops import gram as gram_ops
from twtml_tpu.ops.quality import QUALITY_INDEX
from twtml_tpu.parallel import ParallelSGDModel, make_mesh
from twtml_tpu.parallel import sharding as sharding_module


class _SlicedPlane:
    """``CountPlane`` as PRs 30–53 had it: ONE count matrix ``c`` of all the
    batch's rows, and this shard's rows a dynamic slice of it (or of a
    ``[B]`` vector reduced from all of it)."""

    def __init__(self, whole, row_start, rows: int):
        self._whole = whole  # the one-device plane: c_own is all of C
        self._start, self._rows = row_start, rows

    def _left(self, x):
        if self._rows:
            return lax.dynamic_slice_in_dim(x, self._start, self._rows, axis=0)
        return x

    def dot(self, w):
        if w.ndim == 2:
            return jnp.stack([self.dot(w_m) for w_m in w])
        return self._left(self._whole.dot(w))

    def tdot(self, alpha):
        whole = self._whole
        panel = gram_ops.CountPlane(
            self._left(whole.c_own), None, None, whole._f_text)
        return panel.tdot(alpha)

    def gram(self):
        whole = self._whole
        with jax.named_scope("gram_matmul"):
            return lax.dot_general(
                self._left(whole.c_own), whole.c_own,
                ((whole._features, whole._features), ((), ())),
                **whole._product,
            ).astype(jnp.float32)


def _parents_text_gram(token_idx, token_val, f_text, row_start=None,
                       rows: int = 0, *, body, **kw):
    """``text_gram`` with the parent's row panel: the gate and the builders
    as they stand (no panel asked for: one C), ``body`` on the sliced
    plane."""
    return gram_ops.text_gram(
        token_idx, token_val, f_text, **kw,
        body=lambda whole: body(_SlicedPlane(whole, row_start, rows)))


def _same(got, want, plane: str, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype, what
    if plane == "exact":
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5, err_msg=str(what))
    else:
        assert got.tobytes() == want.tobytes(), what


# ---------------------------------------------------------------------------
# (a) the three contractions of every data shard

def _pairs(plane: str):
    """``[ROWS, L]`` (idx, val) pairs the gate sends to ``plane``, every
    row different (row ``r`` also holds feature ``r`` once more than any
    other row does)."""
    rng = np.random.default_rng(54 + PLANES[plane])
    slots = 300
    idx = rng.integers(ROWS, F_TEXT, (ROWS, slots)).astype(np.int32)
    val = np.zeros((ROWS, slots), np.float32)
    val[:, :{"s8": 100, "bf16": 290, "exact": 290}[plane]] = 1.0
    idx[:, 0] = np.arange(ROWS)
    if plane == "exact":
        val[5, 7] = 0.5  # a fraction: neither integer plane may take it
    return jnp.asarray(idx * (val > 0)), jnp.asarray(val)


def _panels(gram_fn, idx, val, w, alpha, shards: int):
    """(G panel, ``u``, delta, plane) of every data shard, stacked."""
    mesh = make_mesh(num_data=shards, num_model=1,
                     devices=jax.devices()[:shards])
    (axis,) = mesh.axis_names
    rows = ROWS // shards

    def shard(i, v, w, alpha):
        k = lax.axis_index(axis)
        mine = lax.dynamic_slice_in_dim(alpha, k * rows, rows)

        def body(counts):
            return counts.gram(), counts.dot(w), counts.tdot(mine)

        (g, u, delta), plane = gram_fn(
            i, v, F_TEXT, row_start=k * rows, rows=rows, body=body)
        return g[None], u[None], delta[None], plane[None]

    out = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P(),) * 4, out_specs=(P(axis),) * 4,
    ))(idx, val, w, alpha)
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("plane", sorted(PLANES))
def test_every_shards_panel_is_the_slice_of_one_count_matrix(plane, shards):
    idx, val = _pairs(plane)
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=F_TEXT).astype(np.float32))
    alpha = jnp.asarray(rng.normal(size=ROWS).astype(np.float32))
    got = _panels(gram_ops.text_gram, idx, val, w, alpha, shards)
    want = _panels(_parents_text_gram, idx, val, w, alpha, shards)
    assert got[3].tolist() == want[3].tolist() == [PLANES[plane]] * shards
    for name, a, b in zip(("g", "u", "delta"), got, want):
        _same(a, b, plane, (name, plane, shards))
    # and it is the batch's G, columns in the batch's order: the shards'
    # panels stacked are symmetric with the row masses on the diagonal,
    # and no two shards hold the same panel
    g = got[0].reshape(ROWS, ROWS)
    np.testing.assert_allclose(g, g.T, rtol=1e-6)
    counts = np.asarray(gram_ops.densify_text(idx, val, F_TEXT), np.float64)
    np.testing.assert_allclose(g, counts @ counts.T, rtol=1e-6)
    assert len({p.tobytes() for p in got[0]}) == shards


# ---------------------------------------------------------------------------
# (b) the steps that ask for a panel

_KW = dict(num_text_features=F_TEXT, num_iterations=50, quality=True)
_STEPS = {
    "2x2": ((2, 2), dict(step_size=0.005, l2_reg=0.1)),
    "4x1": ((4, 1), dict(step_size=0.005, l2_reg=0.1)),
    "2x2-arms": ((2, 2), dict(arms=(STEPS, L2S))),
}


def _run(layout, kw, batches):
    mesh = make_mesh(num_data=layout[0], num_model=layout[1],
                     devices=jax.devices()[:4])
    model = ParallelSGDModel(mesh, **_KW, **kw)
    shape = (4, F_TEXT + 4) if "arms" in kw else (F_TEXT + 4,)
    rng = np.random.default_rng(3)
    model.set_initial_weights((rng.normal(size=shape) * 0.3).astype(np.float32))
    outs = [jax.device_get(model.step(model.pack_for_wire(rb)))
            for rb in batches]
    return outs, model.latest_weights


@pytest.mark.parametrize("step", sorted(_STEPS))
@pytest.mark.parametrize("plane", sorted(PLANES))
def test_mesh_step_is_the_step_on_the_sliced_count_matrix(
    plane, step, monkeypatch
):
    """Three batches through each mesh step as it stands and through the
    same step with the parent's sliced plane in ``text_gram``'s place:
    the new weights, the predictions (this shard's rows of ``u``, rounded)
    and every other leaf of the output."""
    _g, _chunk, batches = _stream_for(plane)
    layout, kw = _STEPS[step]
    outs, weights = _run(layout, kw, batches)
    for module in (sharding_module, sgd_module):
        monkeypatch.setattr(module, "text_gram", _parents_text_gram)
    parent_outs, parent_weights = _run(layout, kw, batches)
    took = np.asarray(outs[0].quality)[..., QUALITY_INDEX["gram_plane"]]
    assert (took == PLANES[plane]).all()
    _same(weights, parent_weights, plane, (step, "weights"))
    for n, (out, parent_out) in enumerate(zip(outs, parent_outs)):
        for name, a, b in zip(out._fields, out, parent_out):
            if a is None and b is None:
                continue   # a leaf this learner does not have (``primal``)
            _same(a, b, plane, (step, n, name))
