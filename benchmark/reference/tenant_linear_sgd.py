"""Plain reference of the TENANT plane of the streaming linear learner:
NumPy, float64 (configuration ``hash2e18-ab4``: ``--tenants M``, hash
routing).

M learners on one stream. Every kept line of a batch is ROUTED to one of
them by a key of its text; each learner then sees, batch by batch, only its
own rows, in their original order, and makes ``linear_sgd.LinearSGD``'s step
on them (predict with the pre-update weights, then train: MLlib's
``GradientDescent`` with ``LeastSquaresGradient`` and ``SquaredL2Updater``,
its own copy of everything in ``linear_sgd.py``). The upstream reference
(QilinGu/twitter-stream-ml) has no tenants: the plane is this project's own
documented setting (README.md, "multi-tenant model plane (r10)"), and what
is held here is its stated law, that each tenant's model is the single
model's on that tenant's rows.

THE ROUTING RULE, written out here and imported from nowhere
(``--tenantKey hash``; the program's copy is
``twtml_tpu/features/batch.tenant_route_keys``): over the UTF-16 code units
``u_1..u_L`` of the row's text AS THE RAGGED WIRE CARRIES IT,

    x  = (Σ u_i) · 0x9E3779B97F4A7C15 + L · 0xBF58476D1CE4E5B9   (mod 2^64)
    x ^= x >> 33;  x *= 0xFF51AFD7ED558CCD (mod 2^64);  x ^= x >> 33
    tenant = x mod M

(the finaliser is the first half of SplitMix64's / MurmurHash3's ``fmix64``:
one multiply, two shifts). "As the wire carries it" is a FINDING of PR 35 and
not a nicety: the trainer ships an all-ASCII text with its case KEPT (the
device folds ASCII case inside the step) and a text holding any unit ≥ 128
already lower-cased by Unicode's rule on the host (``str.lower``, whose
result may be longer: ``İ``), so the key of an ASCII row reads its capitals
and the key of any other row does not. The narrow (one byte a unit) form of
the wire holds the same values and changes no sum. A routing key that read
the lower-cased text for every row would differ from the program's on every
ASCII row with a capital letter (8% of the generator's ASCII words are
capitalised); PERF.md section 6 states it, ROADMAP R12 carries it.

Per batch it reports what the app prints for the batch
(``parallel/tenants.aggregate_tenant_output``): ``count``, the rows of all
tenants, and ``mse``, the mean over all rows of the squared error of each
row's HALF_UP-rounded prediction by ITS OWN tenant's pre-update weights —
the row-weighted mean of the tenants' unrounded mses, HALF_UP-rounded once.

A tenant with NO row in a batch keeps its weights as they are: no gradient
step and NO L2 shrink (the program's step on an all-padding batch is a state
no-op; ``benchmark/tests/test_hash2e18_ab4.py`` holds both sides to it).

``.w`` is ``[M, F+4]``, the stacked checkpoint's layout. ``precision="bf16"``
is the CONTROL (``linear_sgd``'s rounding of every product's floating
operands, inside each tenant's update), not a reference.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import linear_sgd

MASK64 = (1 << 64) - 1
GOLDEN = 0x9E3779B97F4A7C15
LENGTH_MUL = 0xBF58476D1CE4E5B9
FMIX_MUL = 0xFF51AFD7ED558CCD


def wire_text(text: str) -> str:
    """The text whose code units the trainer's ragged wire carries: an
    all-ASCII text as it is (case kept), any other lower-cased."""
    return text if text.isascii() else text.lower()


def route(text: str, num_tenants: int) -> int:
    """The tenant of one row, in Python's own integers (no overflow to
    reason about)."""
    units = np.frombuffer(
        wire_text(text).encode("utf-16-le", "surrogatepass"), dtype="<u2"
    )
    x = (int(units.sum(dtype=np.uint64)) * GOLDEN
         + int(units.size) * LENGTH_MUL) & MASK64
    x ^= x >> 33
    x = (x * FMIX_MUL) & MASK64
    x ^= x >> 33
    return x % int(num_tenants)


class TenantLinearSGD:
    """M ``LinearSGD`` learners with one set of hyper-parameters (what the
    CLI gives: ``TenantStackModel.from_conf``) and the batch-level stats."""

    def __init__(self, num_tenants, num_text_features, **learner):
        self.m = int(num_tenants)
        self.f = int(num_text_features)
        self.tenants = [
            linear_sgd.LinearSGD(self.f, **learner) for _ in range(self.m)
        ]

    @property
    def w(self) -> np.ndarray:
        return np.stack([t.w for t in self.tenants])

    def step_batch(self, texts, followers, favourites, friends, created_ms,
                   retweets, now_ms) -> dict:
        ids = np.array([route(t, self.m) for t in texts], dtype=np.int64)
        cols5 = [np.asarray(c) for c in
                 (followers, favourites, friends, created_ms, retweets)]
        rows_of, sq_err = [], 0.0
        for m, learner in enumerate(self.tenants):
            mine = np.flatnonzero(ids == m)      # original order kept
            rows_of.append(int(mine.size))
            if not mine.size:
                continue                         # dry: the state stays
            fo, fa, fr, cr, rt = (c[mine] for c in cols5)
            rows, cols, numeric = linear_sgd.featurize(
                [texts[i] for i in mine], fo, fa, fr, cr, now_ms, self.f)
            y = rt.astype(np.float64)
            sq_err += float(np.sum(
                (y - learner.predict(rows, cols, numeric)) ** 2))
            learner.step_batch(rows, cols, numeric, y)
        n = len(texts)
        return {
            "count": int(n),
            "mse": float(linear_sgd.half_up(sq_err / max(n, 1))),
            "tenant_rows": rows_of,
        }


def train_on_chunks(chunks, *, batch_rows, n_batches, model, generator,
                    precision="float64"):
    """The ONE signature every reference has. The number of tenants is the
    configuration's ``model.tenants``."""
    learner = TenantLinearSGD(
        model["tenants"], model["numTextFeatures"],
        num_iterations=model["numIterations"], step_size=model["stepSize"],
        l2_reg=model["l2Reg"], precision=precision,
    )
    out = [
        learner.step_batch(*batch, now_ms=generator["now_ms"])
        for batch in linear_sgd.kept_batches(chunks, batch_rows, n_batches)
    ]
    return learner, out
