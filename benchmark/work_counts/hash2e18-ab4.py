"""Work one train step of the TENANT plane NEEDS (``hash2e18-ab4``: M
hash-routed learners on one batch of B rows; ``hash2e18-lang4`` takes it by
name).

Each tenant needs the Gram of ITS OWN rows, 2·n_m²·F with Σ n_m = B. By
convexity Σ n_m² ≥ B²/M, reached by the even split that hash routing gives
on average, so the floor is 2·B²·F ÷ M: a QUARTER of one single-model step at
M = 4 (``work_counts/hash2e18.py``), against the int8 peak for the same
reason as there (the s8 plane is the fastest the program has). Bytes: every
row's one-hot counts are written once and read once as each operand whoever
owns the row (3·B·F), plus the tenant wire as it was sent and the M Gram
matrices of (B/M)² f32. The dual loops and write-backs are left out (a lower
bound).

The program SPENDS M·2·R²·F, R the row rung ``split_batch_tenants`` pads
every part to (``features/batch.tenant_row_rungs``, PR 36; the Gram step's
cost does not depend on its mask): under the hash key's even split R = 640
at B = 2,048 and M = 4, 0.39 of the single model's operations, and
``step_roofline`` reads ~16.5%, bytes-bound; under a lopsided key
(``hash2e18-lang4``) every part takes R = B, the program spends M·2·B²·F as
it did everywhere until PR 36, and the share reads ~3%. The share cannot
pass 100%: no schedule computes M Grams of Σ n_m = B rows in fewer
operations than the even split's.
"""


def work(config: dict, chips: int, wire_bytes_per_batch: float) -> dict:
    m = config["model"]
    b = float(config["batch_rows"])
    f = float(m["numTextFeatures"])
    tenants = float(m["tenants"])
    flops = 2.0 * b * b * f / tenants / chips
    nbytes = (3 * b * f + wire_bytes_per_batch
              + tenants * (b / tenants) ** 2 * 4) / chips
    return {"flops": flops, "bytes": nbytes, "peak": "int8_ops"}
