"""Work one train step of the hashed learner NEEDS, per chip (``hash2e18``
on one chip, and ``hash2e20`` over its four through the file's
``work_count``).

FLOPs: the full text Gram ``G = Z·Zᵀ``, 2·B²·F, counted against the INT8
peak because the s8×s8→s32 plane is the fastest the program has
(``ops/gram.py``: a batch whose rows all hold at most 127 bigrams takes it;
the benchmark's mixes take the bf16 plane, by rung 1 or by rung 2 of its
gate, PERF.md section 4, and are held to the same floor; no cell takes the
exact f32 plane since PR 25); the [B]-sized dual loop and the
write-back are left out (lower bound). Bytes: the one-hot densify writes the
``[B, F]`` s8 count matrix once and the matmul reads it once as each
operand, plus the packed wire and ``G`` in f32. At B = 2048 that is
2.2 TFLOP => a 5.6 ms floor, compute-bound.

A PR that halves the Gram's work by symmetry makes this count stale: it
then needs a ``benchmark`` issue to restate it (PERF.md section 7).
"""


def work(config: dict, chips: int, wire_bytes_per_batch: float) -> dict:
    m = config["model"]
    b = float(config["batch_rows"])
    f = float(m["numTextFeatures"])
    flops = 2.0 * b * b * f / chips
    nbytes = (3 * b * f + wire_bytes_per_batch + b * b * 4) / chips
    return {"flops": flops, "bytes": nbytes, "peak": "int8_ops"}
