"""What the PROGRAM makes of the one-character rows that ``generator.runs``
writes (``gen.run_lines``, PR 34; ``test_contract.py`` holds the generator's
side without jax): both of its parsers read the run lines' truth back, ASCII
and ``\\uXXXX`` alike; its own train step on a generated block takes the exact
plane for a run of 258 units and the bf16 plane for one of 257, and agrees
with the plain reference on both.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_runs_program.py -q
"""

import os

import numpy as np
import pytest

from benchmark import gen, manifest
from benchmark.tests.test_contract import runs_generator


def test_both_parsers_read_the_run_lines_truth_back():
    """ASCII and ``\\uXXXX`` alike: the C parser and the Python ground truth
    (``BlockParserMixin._py_parse``) hand the featurizer the units the
    generator meant."""
    from twtml_tpu.streaming.sources import BlockParserMixin

    class Parser(BlockParserMixin):
        begin, end = 100, 1000

    g = runs_generator(every_blocks=1, lines_per_block=6)
    seed = 2147483659
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0, 4096)
    rows = [i for i, t in enumerate(chunk.text)
            if len(set(t)) == 1 and gen._units(t) >= 258]
    assert len(rows) == 12
    assert {chunk.text[i][0].isascii() for i in rows} == {True, False}
    data = ("\r\n".join(chunk.lines[i] for i in rows) + "\r\n").encode("ascii")
    want = [np.frombuffer(chunk.text[i].encode("utf-16-le"), "<u2")
            for i in rows]
    from_c, rest = Parser()._parse_impl(data)
    from_py, _ = Parser()._py_parse(data)
    assert rest == b"" and from_c.rows == from_py.rows == len(rows)
    for block in (from_c, from_py):
        off = np.asarray(block.offsets)
        for k, w in enumerate(want):
            assert np.array_equal(np.asarray(block.units[off[k]:off[k + 1]]), w)


def _one_batch(g, seed, f_text):
    """A generated block through the program's own parser, featurizer and
    train step (tiny ``numTextFeatures``, the gate's real constants) and
    through the plain reference: (plane taken, weights_dev)."""
    from twtml_tpu.features.blocks import merge_blocks
    from twtml_tpu.features.featurizer import Featurizer
    from twtml_tpu.models import StreamingLinearRegressionWithSGD
    from twtml_tpu.ops.quality import QUALITY_INDEX
    from twtml_tpu.streaming.sources import BlockParserMixin

    class Parser(BlockParserMixin):
        begin, end = g["retweets_min"], g["retweets_max"]

    rows = g["length_block"]
    chunk = gen.make_chunk(g, gen.build_vocab(g, seed), seed, 0, rows)
    cfg = manifest.load_json(os.path.join(
        manifest.HERE, "configs", "hash2e18.json"))
    m = dict(cfg["model"], numTextFeatures=f_text)
    model = StreamingLinearRegressionWithSGD(
        num_text_features=f_text, num_iterations=m["numIterations"],
        step_size=m["stepSize"], l2_reg=m["l2Reg"], quality=True)
    data = ("\r\n".join(chunk.lines) + "\r\n").encode("ascii")
    block = merge_blocks(Parser().parse_buffer(data))
    assert block.rows == rows
    out = model.step(Featurizer(
        num_text_features=f_text, now_ms=g["now_ms"]
    ).featurize_parsed_block(block))
    ref, _stats = manifest.load_module(os.path.join(
        manifest.ROOT, cfg["reference"])).train_on_chunks(
        [chunk], batch_rows=rows, n_batches=1, model=m, generator=g)
    w = np.asarray(model.latest_weights, np.float64)
    dev = float(np.sum(np.abs(w - ref.w)) / np.sum(np.abs(ref.w)))
    return int(out.quality[QUALITY_INDEX["gram_plane"]]), dev, cfg


@pytest.mark.parametrize("case,want", [
    ("plain", 1),      # trimmed-kept-280's block: rung 2 passes, bf16 plane
    ("runs", 0),       # the same block with its run row: the exact plane
    ("all-257", 1),    # a run of 257 units is 256 bigrams: still rung 2's
    ("all-258", 0),    # 258 units, 257 bigrams on one feature: past the gate
])
def test_program_step_takes_the_plane_the_run_row_asks_for(case, want):
    """The program's own step (CPU backend, 16,384 text dims, the least at which it takes the Gram
    basis by itself, 256 rows; the
    gate's constants are the chip's) on a generated block: the plane by the
    ``gram_plane`` index of its quality vector, and the weights against
    ``benchmark/reference/linear_sgd.py`` inside the configuration's own
    ``weights_dev`` limit on either plane."""
    g = runs_generator(every_blocks=1)
    g.update(length_block=256, pool_lines=256)
    if case == "plain":
        del g["runs"]
    elif case.startswith("all-"):   # every line of one length, one of them a run
        n = int(case[4:])
        g.update(text_units_min=n, text_units_max=n, text_units_mean=n,
                 text_units_sd=0)
        g["runs"]["min_units"] = n
    plane, dev, cfg = _one_batch(g, 3000000019, 16384)
    assert plane == want
    assert dev <= cfg["correct"]["limits"]["weights_dev"], dev
