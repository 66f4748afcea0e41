"""Plain reference of the tenant plane under the SCRIPT key: NumPy, float64
(``--tenants M --tenantKey lang``), named by the configuration
``hash2e18-lang4`` (PR 42; the per-script share of its traffic is the
generator's and has no public source: the configuration's ``assumed`` and
PERF.md section 7 say so); tier-1 holds the program to it at CPU sizes too
(``tests/test_tenant_lang_deployment.py``).

M learners on one stream, as ``tenant_linear_sgd.py`` (the hash key's
reference) has them: every kept line of a batch is ROUTED to one learner by
a key of its text; each learner sees, batch by batch, only its own rows in
their original order and makes ``linear_sgd.LinearSGD``'s step on them
(predict with the pre-update weights, then train). What is held is the
plane's stated law: each tenant's model is the single model's on that
tenant's rows. The plane is this project's own documented setting
(README.md, "multi-tenant model plane (r10)"); the upstream reference has no
tenants.

THE ROUTING RULE, written out here and imported from nowhere
(``--tenantKey lang``; the program's copy is
``twtml_tpu/features/batch.tenant_route_keys(mode="lang")``): over the
UTF-16 code units ``u_1..u_L`` of the row's text AS THE RAGGED WIRE CARRIES
IT — an all-ASCII text as it is, any other lower-cased by Unicode's rule
(``str.lower``, whose result may be longer and may hold OTHER units: ``İ``
U+0130 becomes ``i`` + U+0307, ``Ÿ`` U+0178 becomes ``ÿ`` U+00FF) — with a
surrogate pair counted as its TWO units,

    top    = max(u_1..u_L)              (0 for an empty text)
    class  = 0               if top < 128       (all-ASCII, or empty)
             1 + (top >> 8)  otherwise          (1 + the high byte)
    tenant = class mod M

So at M = 4: tenant 0 holds the all-ASCII rows (class 0) and every class ≡ 0
(high byte ≡ 3: combining marks U+03xx, so ``İ``; CJK U+4Fxx, U+53xx, …);
tenant 1 the accented-Latin rows (U+0080–U+00FF: class 1) and the classes
≡ 1 (high byte ≡ 0: CJK U+50xx, U+54xx, …); tenant 2 Latin Extended-A
(U+01xx: class 2) and high bytes ≡ 1 (CJK U+51xx, …); tenant 3 high bytes
≡ 2 (U+02xx; CJK U+4Exx, U+52xx, …) — and EVERY row with an emoji, whose
low surrogate is its largest unit (U+DE00–U+DE4F for U+1F600–U+1F64F: high
byte 0xDE, class 223 ≡ 3). A CJK row's tenant follows the high byte of its
LARGEST ideograph, so one script spreads over all four tenants; the rule is
a cheap split by "what the row's largest unit looks like", not a language
detector (PERF.md section 7; ROADMAP R12 (b)).

Per batch it reports what the app prints for the batch
(``parallel/tenants.aggregate_tenant_output``): ``count``, the rows of all
tenants, and ``mse``, the mean over all rows of the squared error of each
row's HALF_UP-rounded prediction by ITS OWN tenant's pre-update weights,
HALF_UP-rounded once. A tenant with NO row in a batch keeps its weights as
they are: no gradient step and NO L2 shrink (the program's step on an
all-padding batch is a state no-op). ``.w`` is ``[M, F+4]``, the stacked
checkpoint's layout. ``precision="bf16"`` is the CONTROL (``linear_sgd``'s
rounding of every product's floating operands, inside each tenant's
update), not a reference.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import linear_sgd


def wire_text(text: str) -> str:
    """The text whose code units the trainer's ragged wire carries: an
    all-ASCII text as it is (case kept), any other lower-cased."""
    return text if text.isascii() else text.lower()


def script_class(text: str) -> int:
    """The class of one row: 0 under 128, else 1 + the high byte of the
    largest UTF-16 unit of the text as the wire carries it (surrogates as
    units)."""
    units = np.frombuffer(
        wire_text(text).encode("utf-16-le", "surrogatepass"), dtype="<u2"
    )
    top = int(units.max()) if units.size else 0
    return 0 if top < 128 else 1 + (top >> 8)


def route(text: str, num_tenants: int) -> int:
    """The tenant of one row."""
    return script_class(text) % int(num_tenants)


class TenantLangLinearSGD:
    """M ``LinearSGD`` learners with one set of hyper-parameters (what the
    CLI gives: ``TenantStackModel.from_conf``), rows routed by ``route``,
    and the batch-level stats."""

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
    learner = TenantLangLinearSGD(
        model["tenants"], model["numTextFeatures"],
        num_iterations=model["numIterations"], step_size=model["stepSize"],
        l2_reg=model["l2Reg"], precision=precision,
    )
    out = [
        learner.step_batch(*batch, now_ms=generator["now_ms"])
        for batch in linear_sgd.kept_batches(chunks, batch_rows, n_batches)
    ]
    return learner, out
