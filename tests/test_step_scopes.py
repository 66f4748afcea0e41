"""The device stages of the train step carry stable names (PR 24):
``jax.named_scope``s in models/sgd.py and ops/{ragged,text_hash,gram}.py
that ``benchmark/stage_times.py`` sums a profile by. Lowering only — nothing
runs, so the tiny batch can keep hash2e18's 2^18 text dims."""

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
    ``text_gram``'s three planes (the branches of its switch) has its count
    build under ``gram_count`` and its product under ``gram_matmul``: the
    planes share the names, the operand types tell them apart."""
    for scope in STAGE_SCOPES:
        assert any(f"/{scope}/" in n or n.endswith(f"/{scope}")
                   for n in op_names), scope
    inside = [n for n in op_names if f"/branch_{branch}_fun/" in n]
    assert inside, plane
    for scope in ("gram_count", "gram_matmul"):
        assert any(f"/branch_{branch}_fun/{scope}/" in n for n in inside), (
            plane, scope)
    # nothing a branch runs is outside the two names
    assert all("/gram_count/" in n or "/gram_matmul/" in n for n in inside)


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
