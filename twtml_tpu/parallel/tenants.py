"""Multi-tenant model plane: M models, ONE jit program, ONE fetch (ISSUE 7).

The reference trains one global retweet model; the scenario axis (per-topic /
per-language / per-A/B-arm) would naively cost M full pipelines — M wires, M
dispatches, and above all M host fetches per tick (fetch latency, not array
count, is what a per-batch-telemetry run pays). This module stacks M models
along a leading tenant axis so the marginal tenant costs device FLOPs
instead of extra host fetches:

- **weights** are one ``[M, F+4]`` array (one optimizer-state pytree; one
  donated buffer), per-tenant hyperparams (step size, L2) ride as mapped
  scalar leaves of a separate ``hyper`` pytree;
- **the step** maps the EXISTING fused SGD step over the tenant axis with
  ``lax.map`` — a scan of the single-tenant step program with no carry,
  which keeps every tenant's math BIT-IDENTICAL to the reference
  single-model path (the parity law). What a tenant COSTS on the device:
  a step's cost does not depend on its mask, so it is the step at the
  rows its PART is padded to — a tenant row rung
  (``features/batch.tenant_row_rungs``), read off each batch: the smallest
  of a short ladder (1.25·B/M rounded up to 128 rows, doubling, B) that
  holds the part's rows and units. The split takes TWO rungs a batch: the
  fullest tenant's, and for the other M−1 the one the fullest of THEM
  needs. The jitted program specialises on the wire's shape, so a pair of
  rungs is one more program per units bucket, and a uniform key takes the
  first rung for all in every batch: at 2^18 hashed dims M = 4 runs four
  Gram steps of 640 rows a batch of 2,048, 11.9 ms of device time, and the
  HOST sets the pace (107.4k tweets/s where one model trains 116.7k;
  PERF.md §5–§6, PR 36). A lopsided split (``--tenantKey lang``: one
  tenant with ~72% of every batch; a dry-tenant stream) pays the wide rung
  ONCE — the fullest tenant's step at 2,048 rows and the map over three
  parts of 640, both in the ONE program (``_two_rungs``; PERF.md §6, PR 49
  has what that reads on the chip) — where until PR 49 every part took
  the fullest's rung: four steps of 16.97 ms, 30.1k tweets/s (PR 35, PR
  42). A mesh, ``--wirePack group`` and a pinned rung (the multi-host
  fleet) keep ONE rung for all M parts, the fullest's. At the reference's
  1,004 dims not measured on the chip;
- **the wire** is shared: rows route to tenants on the host by a cheap
  deterministic key (``features/batch.tenant_route_keys``), split into M
  batches of their row rungs' shapes (dry tenants = all-padding, the
  lockstep invariant), and ship as ONE M-tenant wire in one dispatch —
  ``stack_batches`` where the parts share a rung, the two-member
  ``TwoRungWire`` (``stack_two_rungs``: ``[1, r_full, ...]`` +
  ``[M−1, r_rest, ...]`` + the tenant ids, a traced value) where the fullest
  takes a wider one (``--wirePack stacked``), or the coalesced one-buffer
  ``pack_ragged_group`` (``--wirePack group``: one rung);
- **the fetch** is one ``jax.device_get`` of the ``[M, ...]`` StepOutput
  through the existing FetchPipeline — fetch count per tick is ONE
  regardless of M (asserted by the counting tests).

``--tenantKey all`` (a champion, tenant 0, and its challengers: ONE learner
under M recipes, ``--tenantStepSize`` / ``--tenantL2Reg``) is the plane
WITHOUT the partition: every tenant trains on every row. Nothing is routed,
split or stacked — the batch ships once as the single-model wire — and the
program is not the map of the whole step either: the arms share their rows,
so they share the count matrix C and G = C·Cᵀ, which are the step's cost,
and ``models/sgd.make_sgd_train_step(arms=True)`` builds both once a batch,
reads C once for all arms in each of its two contractions (``C·[w_1…w_M]``
out of the count build, ``Cᵀ·[α_1…α_M]`` in one pass) and maps only the dual
loop over the arms (scope ``arm_map``, ``lax.map``: arm m is the single
model under arm m's recipe, to float32 rounding in the Gram basis and bit
for bit outside it). State, fetch, checkpoint and frames are the plane's:
``[M, F+4]`` weights, one ``[M, ...]`` StepOutput. THIS class is the
one-device form, on the stacked wire only (refused otherwise, with the
reason); on a mesh with a model axis (``--modelShards``: 2^20 dims, whose
count matrix one chip cannot hold) the arms are the feature-sharded mesh
model's, ``parallel/sharding.ParallelSGDModel(arms=...)``, which carries
this plane's surface under the key and calls the same per-arm half
(``models/sgd.arms_dual_half``).

Mesh composition: a 1D ('data',) mesh shards every tenant batch's rows over
``data`` (tenant axis unsharded — weights replicated) with the per-shard
body's psums riding the existing collectives; a 2D ('data','model') mesh
maps the TENANT axis onto ``model`` (the cross-process model axis proven in
parallel/distributed.py + tests/test_distributed_multiprocess.py): each
model shard holds M/num_model tenants' weights and maps only those — tenant
independence means NO collective ever crosses the model axis.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..features.batch import (
    NUM_NUMBER_FEATURES,
    PackedBatch,
    RaggedUnitBatch,
    TwoRungWire,
    gather_tenant_predictions,
    pack_batch,
    pack_ragged_group,
    split_batch_tenants,
    stack_batches,
    stack_two_rungs,
    tenant_route_keys,
    unpack_batch,
    wire_nbytes,
)
from ..models.base import StepOutput
from ..models.sgd import make_sgd_train_step
from ..telemetry import trace as _trace
from ..utils import get_logger

log = get_logger("parallel.tenants")

# why --tenantKey all has no group wire, wherever it is asked for (here on
# one device, apps/common.build_model on the mesh)
SHARED_ROWS_GROUP_WIRE = (
    "--wirePack group coalesces M tenant batches into one buffer; under "
    "'all' there is ONE batch, shipped as the single-model wire"
)


def aggregate_tenant_output(out, batch, model) -> StepOutput:
    """The delivered ``[M, ...]`` StepOutput → ONE batch-level StepOutput in
    the ORIGINAL batch's row order, for the app handler / sentinel /
    session-stats chain that predates tenants. Pure host numpy on the
    already-fetched arrays — zero added device work or fetches.

    M = 1 passes tenant 0's output through untouched (bit-exact — the M=1
    parity law). M > 1 aggregates: ``count`` sums; ``mse`` is the
    row-weighted mean of per-tenant mses (exact — mse is a per-row mean);
    the stdevs are row-weighted POOLED within-tenant stdevs (each tenant is
    an independent model, so a cross-tenant stdev is not a reference
    quantity; the pooled form is documented in PARITY.md). ``predictions``
    (fetched as ``[M, rung]``, the split's row rung) come back at the
    ORIGINAL batch's row count, re-ordered to original rows via the
    deterministic routing key — the same route the wire used, recomputed
    instead of carried through the fetch pipeline. A non-finite stat in ANY
    tenant propagates into the
    aggregate, so the divergence sentinel still sees every poisoning.
    Under ``--tenantKey all`` nothing was partitioned, so nothing is
    pooled: the line is arm 0's (below).

    ``quality`` (ISSUE 8): M = 1 passes tenant 0's vector through like
    every other leaf; M > 1 leaves the aggregate's quality None — norms of
    M independent models don't pool into one meaningful vector, and the
    model-watch adapter consumes the per-tenant [M, Q] leaf BEFORE this
    aggregation (apps/common.attach_pipeline wrapping order)."""
    m = model.num_tenants
    if m == 1:
        return StepOutput(*(
            None if f is None else f[0] for f in out
        ))
    if getattr(model, "shared_rows", False):
        # --tenantKey all: every arm saw all B rows, so there is nothing
        # to pool — the batch's own line is the CHAMPION's (arm 0), its
        # count the batch's rows (not M·B) and its predictions already in
        # the batch's row order. A non-finite stat in ANY arm turns the
        # line's mse NaN, so the sentinel still sees every poisoning.
        stats = np.stack([
            np.asarray(f, np.float64)
            for f in (out.mse, out.real_stdev, out.pred_stdev)
        ])
        poison = np.float32(0.0 if np.isfinite(stats).all() else np.nan)
        return StepOutput(
            predictions=(
                None if out.predictions is None
                else np.asarray(out.predictions)[0]
            ),
            count=np.float32(np.asarray(out.count)[0]),
            mse=np.float32(np.asarray(out.mse)[0]) + poison,
            real_stdev=np.float32(np.asarray(out.real_stdev)[0]),
            pred_stdev=np.float32(np.asarray(out.pred_stdev)[0]),
        )
    counts = np.asarray(out.count, np.float64)
    total = float(counts.sum())
    denom = max(total, 1.0)
    mse = float((counts * np.asarray(out.mse, np.float64)).sum() / denom)
    real_sd = float(np.sqrt(
        (counts * np.square(np.asarray(out.real_stdev, np.float64))).sum()
        / denom
    ))
    pred_sd = float(np.sqrt(
        (counts * np.square(np.asarray(out.pred_stdev, np.float64))).sum()
        / denom
    ))
    preds = None
    if out.predictions is not None:
        preds = gather_tenant_predictions(
            out.predictions, batch, model.route_ids(batch), m
        )
    return StepOutput(
        predictions=preds,
        count=np.float32(total),
        mse=np.float32(mse),
        real_stdev=np.float32(real_sd),
        pred_stdev=np.float32(pred_sd),
    )


def split_tenant_output(out: StepOutput, num_tenants: int):
    """Host-side split of the ONE fetched ``[M, ...]`` StepOutput into M
    per-tenant StepOutputs (plain numpy views — no further host fetch)."""
    return [
        StepOutput(*(
            None if f is None else f[m] for f in out
        ))
        for m in range(num_tenants)
    ]


class TenantStackModel:
    """M stacked streaming-SGD learners with the single-model step surface
    the pipelines consume (``step``/``latest_weights``/``set_initial_weights``
    /``prepare``/``pack_for_wire``), so FetchPipeline, checkpoints, the
    divergence sentinel and the lockstep scheduler all work unchanged.

    ``step(batch)`` accepts an ORDINARY featurized host batch: it routes the
    rows (``tenant_route_keys`` → ``split_batch_tenants``), builds the
    tenant wire, and runs the one jit program; the returned StepOutput
    carries ``[M]``-leading leaves in TENANT order (``[M, R]`` predictions
    in per-tenant row order, R the FULLEST part's row rung for this batch —
    ``route_ids`` re-derives the original-row permutation on the host). A
    tenant's device cost follows the rung ITS part took: the split reads
    two off each batch, the fullest tenant's and the other M−1's; where
    they differ the wire is a ``TwoRungWire`` and the program runs
    ``_two_rungs`` (one step at the wide rung, the map over the narrow
    ones; one dispatch and one fetch all the same). A pre-routed wire (a
    stacked batch or a ``TwoRungWire`` from ``prepare_wire``, a PackedBatch
    from ``pack_for_wire``) passes straight through — the pack happens
    once, at the model boundary, exactly like the single-tenant packed
    wire."""

    accepts_packed = True

    def __init__(
        self,
        num_tenants: int,
        num_text_features: int = 1000,
        num_iterations: int = 50,
        step_size: float = 0.1,
        mini_batch_fraction: float = 1.0,
        l2_reg: float = 0.0,
        convergence_tol: float = 0.001,
        dtype=jnp.float32,
        residual_fn: Callable | None = None,
        prediction_fn: Callable | None = None,
        round_predictions: bool = True,
        use_sparse: bool | None = None,
        use_gram: bool | None = None,
        tenant_key: str = "hash",
        wire_pack: str = "stacked",
        wire_codec: str = "",
        mesh=None,
        step_sizes=None,
        l2_regs=None,
        quality: bool = False,
    ) -> None:
        if num_tenants < 1:
            raise ValueError(f"num_tenants must be >= 1, got {num_tenants}")
        if wire_pack not in ("stacked", "group"):
            raise ValueError(
                f"wire_pack must be 'stacked' or 'group', got {wire_pack!r}"
            )
        # --tenantKey all: every tenant sees every row, so there is no
        # tenant wire — and no form of it for what a tenant wire carries
        self.shared_rows = tenant_key == "all"
        if self.shared_rows:
            for bad, why in (
                (wire_pack == "group", SHARED_ROWS_GROUP_WIRE),
                (mesh is not None,
                 "this stack is the ONE-device form of the arms (the map "
                 "runs inside one device's Gram branch): use --master "
                 "local[1]. On a mesh with a model axis (--modelShards) the "
                 "arms are parallel/sharding.ParallelSGDModel(arms=...); "
                 "the data-only mesh has no form of the per-arm half"),
            ):
                if bad:
                    raise ValueError(f"--tenantKey all: {why}")
        self.num_tenants = num_tenants
        self.num_text_features = num_text_features
        self.dtype = dtype
        self.tenant_key = tenant_key
        self.wire_pack = wire_pack
        # compressed units wire on the coalesced tenant wire (r15,
        # --wireCodec): the group pack digram-compresses each tenant
        # segment; "" / "off" = raw. Stacked wire ships raw by design
        # (the codec rides the packed one-buffer forms only).
        self.wire_codec = wire_codec
        self.mesh = mesh
        # --modelWatch: the mapped step computes each tenant's quality
        # vector inside the one jit program — the stacked [M, Q] leaf rides
        # the existing ONE stacked fetch, so per-tenant quality is free
        self.quality = quality
        f_total = num_text_features + NUM_NUMBER_FEATURES

        # per-tenant hyperparams as MAPPED scalar leaves: they are consumed
        # only inside jnp arithmetic (eta = step/√i, the L2 pre-scale), so a
        # traced per-tenant scalar flows through the existing step builder
        # unchanged. Structural knobs (num_iterations, miniBatchFraction,
        # convergenceTol) stay shared — they shape the compiled program.
        def _vec(v, default):
            # made on the host: ``jnp.asarray(list, dtype)`` compiles a
            # ``convert_element_type`` of its own at every process start
            # (0.55 s on the v5e's host; PERF.md §6, PR 49)
            if v is None:
                v = np.full((num_tenants,), default)
            v = jnp.asarray(np.asarray(v, dtype))
            if v.shape != (num_tenants,):
                raise ValueError(
                    f"per-tenant hyperparam needs shape ({num_tenants},), "
                    f"got {v.shape}"
                )
            return v

        self._hyper = {
            "step_size": _vec(step_sizes, step_size),
            "l2_reg": _vec(l2_regs, l2_reg),
        }

        # the EXISTING fused step under a tenant's (traced) hyperparams —
        # the parity-critical semantics live in models/sgd.py exactly once
        step_kw = dict(
            num_text_features=num_text_features,
            num_iterations=num_iterations,
            mini_batch_fraction=mini_batch_fraction,
            convergence_tol=convergence_tol,
            residual_fn=residual_fn,
            prediction_fn=prediction_fn,
            round_predictions=round_predictions,
            use_sparse=use_sparse,
            use_gram=use_gram,
            quality=quality,
        )

        def one(weights, hyper, batch):
            # ONE tenant's step on its own part (mapped by ``_mapped``)
            return make_sgd_train_step(
                step_size=hyper["step_size"], l2_reg=hyper["l2_reg"],
                axis_name=self._data_axis, **step_kw,
            )(weights, batch)

        def shared(weights, hyper, batch):
            # --tenantKey all: ONE step over the batch every arm sees, the
            # arms' dual loops mapped inside it (models/sgd.py ``arms``)
            return make_sgd_train_step(
                step_size=hyper["step_size"], l2_reg=hyper["l2_reg"],
                arms=True, **step_kw,
            )(weights, batch)

        self._one = one
        self._shared = shared
        # host zeros, uploaded: ``jnp.zeros`` compiles a program to make them
        self._weights = jnp.asarray(np.zeros((num_tenants, f_total), dtype))
        self._progs: dict = {}
        # under --trace: each in-flight batch's M rungs (``take_buckets``)
        self._buckets: dict = {}
        if mesh is not None:
            self._init_mesh(mesh)

    # -- mesh plumbing ------------------------------------------------------
    @property
    def _data_axis(self):
        return self.mesh.axis_names[0] if self.mesh is not None else None

    @property
    def _tenant_axis(self):
        """The mesh axis the TENANT dim shards over: the 'model' axis of a
        2D mesh (cross-process tenants), None on 1D (replicated)."""
        if self.mesh is not None and len(self.mesh.axis_names) > 1:
            return self.mesh.axis_names[1]
        return None

    def _init_mesh(self, mesh) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        t_axis = self._tenant_axis
        self.num_data = mesh.shape[self._data_axis]
        if t_axis is not None:
            n_t = mesh.shape[t_axis]
            if self.num_tenants % n_t:
                raise ValueError(
                    f"{self.num_tenants} tenants not divisible by the "
                    f"mesh's {t_axis} axis ({n_t})"
                )
            w_spec = P(t_axis, None)
            self._weights = jax.device_put(
                np.asarray(self._weights), NamedSharding(mesh, w_spec)
            )
            self._hyper = jax.device_put(
                self._hyper,
                NamedSharding(mesh, P(t_axis)),
            )
            self._w_spec, self._h_spec = w_spec, P(t_axis)
            self._out_specs = StepOutput(
                predictions=P(t_axis, self._data_axis),
                count=P(t_axis), mse=P(t_axis),
                real_stdev=P(t_axis), pred_stdev=P(t_axis),
                # [M, Q]: tenant axis sharded like the other stacked leaves
                quality=P(t_axis) if self.quality else None,
            )
        else:
            self._w_spec, self._h_spec = P(), P()
            self._out_specs = StepOutput(
                predictions=P(None, self._data_axis),
                count=P(), mse=P(), real_stdev=P(), pred_stdev=P(),
                # [M, Q] psum-global over data, replicated like the scalars
                quality=P() if self.quality else None,
            )

    def _batch_spec(self, batch_cls):
        from jax.sharding import PartitionSpec as P

        from .sharding import _pspecs_for, _stacked

        t_axis = self._tenant_axis
        if batch_cls is PackedBatch:
            # the coalesced tenant wire is shard-major ([S, M, seg] flat):
            # P(data) hands each device its own M segments (1D mesh only —
            # the 2D tenant layout ships the stacked wire)
            return P(self._data_axis)
        spec = _stacked(_pspecs_for(batch_cls, self._data_axis))
        if t_axis is not None:
            # tenants over the model axis: replace the leading None
            spec = jax.tree_util.tree_map(
                lambda s: P(*((t_axis,) + tuple(s)[1:])),
                spec, is_leaf=lambda x: isinstance(x, P),
            )
        return spec

    # -- the one mapped program ---------------------------------------------
    def _mapped(self, weights, hyper, batch):
        if isinstance(batch, TwoRungWire):
            return self._two_rungs(weights, hyper, batch)
        if isinstance(batch, PackedBatch):
            # coalesced tenant wire (pack_ragged_group): rebuild the
            # stacked [M, ...] leaves in-program — zero-copy bitcasts
            batch = unpack_batch(batch.buffer, batch.layout)
        # one scope around the mapped body: a profile shows the M
        # iterations under ``tenant_map``, with the step's nine stage scopes
        # (models/sgd.STAGE_SCOPES) inside it unchanged
        with jax.named_scope("tenant_map"):
            # lax.map = scan of the single-tenant step with no carry: the
            # SAME program per tenant, hence bit-identical math (the parity
            # law)
            return lax.map(
                lambda args: self._one(*args), (weights, hyper, batch)
            )

    def _two_rungs(self, weights, hyper, wire):
        """A lopsided split's two members in the ONE program: the fullest
        tenant's step on its part at its rung, then ``lax.map`` of the step
        over the others' parts at theirs — each tenant's weights and
        hyper-parameters gathered by its TRACED id (which tenant is fullest
        is a value of the wire, not a shape of the program), both halves
        under ``tenant_map`` with the step's nine stage scopes inside. The
        new ``[M, F+4]`` weights and every ``[M, ...]`` leaf of the ONE
        StepOutput come back in TENANT order (one gather by the inverse of
        ``wire.ids``: no scatter into the donated state), the rest's
        predictions zero-padded to the fullest's rung, so
        everything downstream of the fetch reads what the one-rung program
        gives it. Every tenant's step is still ``make_sgd_train_step``'s own
        program at its part's shape (the parity law)."""
        first, others = wire.ids[0], wire.ids[1:]
        order = jnp.argsort(wire.ids)  # tenant m's row in full ++ rest
        with jax.named_scope("tenant_map"):
            full = self._one(
                weights[first],
                {k: v[first] for k, v in hyper.items()},
                jax.tree_util.tree_map(lambda a: a[0], wire.full),
            )
            rest = lax.map(
                lambda args: self._one(*args),
                (
                    weights[others],
                    {k: v[others] for k, v in hyper.items()},
                    wire.rest,
                ),
            )

        def in_tenant_order(a, b):
            # a: the fullest tenant's leaf; b: the others', [M-1, ...],
            # padded up to a's shape (predictions: r_rest → r_full rows)
            pad = [(0, 0)] + [
                (0, wide - narrow) for wide, narrow in zip(a.shape, b.shape[1:])
            ]
            return jnp.concatenate([a[None], jnp.pad(b, pad)])[order]

        return jax.tree_util.tree_map(in_tenant_order, full, rest)

    def _prog_for(self, batch_cls) -> Callable:
        fn = self._progs.get(batch_cls)
        if fn is None:
            if self.shared_rows:
                fn = jax.jit(self._shared, donate_argnums=0)
            elif self.mesh is None:
                fn = jax.jit(self._mapped, donate_argnums=0)
            else:
                sharded = jax.shard_map(
                    self._mapped,
                    mesh=self.mesh,
                    in_specs=(
                        self._w_spec, self._h_spec,
                        self._batch_spec(batch_cls),
                    ),
                    out_specs=(self._w_spec, self._out_specs),
                )
                fn = jax.jit(sharded, donate_argnums=0)
            self._progs[batch_cls] = fn
        return fn

    # -- routing + wire ------------------------------------------------------
    def route_ids(self, batch) -> np.ndarray:
        """Per-row tenant ids for a host batch — deterministic, so delivery-
        side consumers (per-tenant stats, prediction re-ordering) recompute
        it instead of threading a permutation through the fetch pipeline.
        ``all`` routes nothing and has no ids."""
        return tenant_route_keys(batch, self.num_tenants, self.tenant_key)

    def split(self, batch, rung: int = 0):
        """Route + split into the M tenant batches, padded to the row rungs
        the batch calls for — the fullest tenant's part to its own, the
        others to theirs (``split_batch_tenants``) — or all to ``rung`` when
        pinned. ONE rung for all, the fullest's, where this plane's wire or
        program has one shape for every tenant: the coalesced group buffer
        (``--wirePack group``), a mesh (whose rungs are multiples of its data
        axis)."""
        return split_batch_tenants(
            batch, self.route_ids(batch), self.num_tenants,
            row_multiple=self.num_data if self.mesh is not None else 1,
            rung=rung,
            one_rung=self.mesh is not None or self.wire_pack == "group",
        )

    def _is_tenant_wire(self, batch) -> bool:
        if isinstance(batch, (PackedBatch, TwoRungWire)):
            return True
        mask = getattr(batch, "mask", None)
        return mask is not None and getattr(mask, "ndim", 1) == 2

    def prepare_wire(self, batch):
        """Host batch → the stacked/coalesced M-tenant wire.
        ``--wirePack group`` coalesces the M ragged batches into ONE
        contiguous buffer (one main-thread put, uint16-delta offsets);
        ``stacked`` ships M per-field arrays. Bit-identical leaves either
        way (the wire law, tests/test_superwire.py). Under ``--trace`` the
        whole of it — route key, M-way split, stack or pack — is one
        ``tenant_split`` span on the scheduler's thread (inside
        ``wire_pack``), carrying the routed rows, M and the tenant wire's
        bytes. Under ``--tenantKey all`` there is no route, no split and no
        stack, so no span: the ragged batch is packed as the single model's
        is (``pack_batch``: one buffer, one upload), any other ships as it
        is."""
        if self.shared_rows:
            if isinstance(batch, RaggedUnitBatch):
                return pack_batch(
                    batch, codec="dict" if self.wire_codec == "dict" else None
                )
            return batch
        tr = _trace.get()
        with tr.span("tenant_split", tenants=self.num_tenants) as sp:
            parts = self.split(batch)
            wire = self.prepare_wire_from_parts(parts)
            if tr.enabled:
                buckets = [int(p.mask.shape[0]) for p in parts]
                sp.add(rows=int(batch.num_valid), bytes=wire_nbytes(wire),
                       rungs=(max(buckets), min(buckets)))
                # for this batch's ``tenant_rows`` instant, which is written
                # when its fetch delivers (apps/common.attach_pipeline);
                # far more kept than are ever in flight: deliveries were
                # skipped, and what they left goes
                if len(self._buckets) > 64:
                    self._buckets.clear()
                self._buckets[_trace.current_batch()] = buckets
        return wire

    def take_buckets(self, seq) -> "list[int] | None":
        """The M row rungs, in tenant order, of the split that made batch
        ``seq``'s wire — kept from ``prepare_wire`` while tracing is on, and
        given out ONCE, at the batch's delivery. None where the wire was not
        made here (a pre-routed wire, the multi-host fleet): the caller
        reads the fetched leaf's one rung."""
        return self._buckets.pop(seq, None)

    def prepare_wire_from_parts(self, parts):
        """The wire-layout half of ``prepare_wire`` for callers that route
        themselves (tests, custom routers): M same-signature per-tenant
        batches → the stacked/coalesced tenant wire; the parts of a
        lopsided split, which have two row rungs → the two-member
        ``TwoRungWire`` (``features/batch.stack_two_rungs``)."""
        if len({p.mask.shape[0] for p in parts}) > 1:
            return stack_two_rungs(parts)
        if self.mesh is not None:
            # ragged parts shard-align to the data axis BEFORE stacking
            # (alignment is a flat-batch operation)
            parts = self._align_parts(parts)
        if (
            self.wire_pack == "group"
            and isinstance(parts[0], RaggedUnitBatch)
            # the coalesced shard-major buffer has no tenant-axis layout;
            # the 2D (tenants-on-model-axis) plane ships the stacked wire
            and self._tenant_axis is None
        ):
            codec = self.wire_codec or None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                pb = pack_ragged_group(parts, codec=codec)
                # the host buffer's arena lease rides to the dispatch
                # pipeline (retired on fetch delivery — apps/common.py)
                return PackedBatch(
                    jax.device_put(
                        pb.buffer,
                        NamedSharding(self.mesh, P(self._data_axis)),
                    ),
                    pb.layout,
                )._with_lease(pb._lease)
            return pack_ragged_group(parts, codec=codec)
        return stack_batches(parts)

    def _align_parts(self, parts):
        from ..features.batch import align_ragged_shards, ragged_shard_bucket

        if (
            not isinstance(parts[0], RaggedUnitBatch)
            or parts[0].num_shards == self.num_data
        ):
            return parts
        # one per-shard unit capacity for all M parts (the fullest's), or
        # they would not stack
        bucket = max(ragged_shard_bucket(p, self.num_data) for p in parts)
        return [
            align_ragged_shards(p, self.num_data, unit_bucket=bucket)
            for p in parts
        ]

    # FetchPipeline's pack hook: the tenant wire IS the pack (one routed
    # wire per batch, built once at the model boundary)
    def pack_for_wire(self, batch):
        return self.prepare_wire(batch)

    # -- model surface -------------------------------------------------------
    def step(self, batch) -> StepOutput:
        wire = (
            batch if self.shared_rows or self._is_tenant_wire(batch)
            else self.prepare_wire(batch)
        )
        if self.mesh is not None and not isinstance(
            jax.tree_util.tree_leaves(wire)[0], jax.Array
        ):
            wire = self._place(wire)
        self._weights, out = self._prog_for(type(wire))(
            self._weights, self._hyper, wire
        )
        return out

    def _place(self, wire):
        from jax.sharding import NamedSharding, PartitionSpec as P

        if isinstance(wire, PackedBatch):
            return PackedBatch(
                jax.device_put(
                    wire.buffer, NamedSharding(self.mesh, P(self._data_axis))
                ),
                wire.layout,
            )
        if self._tenant_axis is None:
            # 1D mesh: tenants unsharded, rows over data — the stacked
            # placement shard_batch implements
            from .sharding import shard_batch

            return shard_batch(wire, self.mesh)
        spec = self._batch_spec(type(wire))
        if isinstance(wire, RaggedUnitBatch):
            sharding = NamedSharding(self.mesh, spec)  # one prefix spec
            return RaggedUnitBatch(
                *(jax.device_put(a, sharding) for a in (
                    wire.units, wire.offsets, wire.numeric, wire.label,
                    wire.mask,
                )),
                row_len=wire.row_len, num_shards=wire.num_shards,
            )
        return type(wire)(*(
            jax.device_put(a, NamedSharding(self.mesh, s))
            for a, s in zip(
                wire,
                jax.tree_util.tree_leaves(
                    spec, is_leaf=lambda x: isinstance(x, P)
                ),
            )
        ))

    @staticmethod
    def _to_host(arr) -> np.ndarray:
        if (
            isinstance(arr, jax.Array)
            and not arr.is_fully_addressable
            and not arr.is_fully_replicated
        ):
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True)
            )
        return np.asarray(arr)

    @property
    def latest_weights(self) -> np.ndarray:
        """[M, F+4] — one checkpointable array for all tenants."""
        return self._to_host(self._weights)

    def tenant_weights(self, m: int) -> np.ndarray:
        return self.latest_weights[m]

    def set_initial_weights(self, weights) -> "TenantStackModel":
        """Accepts the stacked [M, F+4] state (checkpoint restore) or one
        flat [F+4] vector broadcast to every tenant (the sentinel's
        zeros-reset, and MLlib-style shared initial weights)."""
        weights = np.asarray(weights, dtype=self.dtype)
        if weights.ndim == 1:
            weights = np.broadcast_to(
                weights, (self.num_tenants,) + weights.shape
            ).copy()
        if weights.shape[0] != self.num_tenants:
            raise ValueError(
                f"stacked weights lead with {weights.shape[0]} tenants; "
                f"this plane has {self.num_tenants}"
            )
        if self.mesh is not None and self._tenant_axis is not None:
            from jax.sharding import NamedSharding

            sharding = NamedSharding(self.mesh, self._w_spec)
            self._weights = jax.make_array_from_callback(
                weights.shape, sharding, lambda idx: weights[idx]
            )
        else:
            self._weights = jnp.asarray(weights)
        return self

    def reset(self) -> "TenantStackModel":
        return self.set_initial_weights(
            np.zeros(
                (self.num_text_features + NUM_NUMBER_FEATURES,), np.float32
            )
        )

    @classmethod
    def from_conf(cls, conf, mesh=None, **overrides):
        key = getattr(conf, "tenantKey", "hash")
        if key == "all":
            # only an EXPLICIT group asks for the coalesced tenant wire
            # here (and is refused): the codec's auto resolution to it is
            # for tenant wires, and under ``all`` there is none
            group = conf.wirePack == "group"
        else:
            group = (
                getattr(conf, "effective_wire_pack", lambda: "stacked")()
                == "group" and conf.effective_wire() == "ragged"
            )
        kwargs = dict(
            num_tenants=int(getattr(conf, "tenants", 1) or 1),
            num_text_features=conf.numTextFeatures,
            num_iterations=conf.numIterations,
            step_size=conf.stepSize,
            mini_batch_fraction=conf.miniBatchFraction,
            l2_reg=conf.l2Reg,
            # --tenantStepSize / --tenantL2Reg: a recipe per tenant
            **dict(zip(("step_sizes", "l2_regs"), conf.tenant_recipes())),
            convergence_tol=conf.convergenceTol,
            dtype=jnp.dtype(conf.dtype),
            tenant_key=key,
            wire_pack="group" if group else "stacked",
            wire_codec=(
                getattr(conf, "effective_wire_codec", lambda: "off")()
            ),
            mesh=mesh,
            quality=getattr(conf, "modelWatch", "off") == "on",
        )
        kwargs.update(overrides)
        return cls(**kwargs)

    def train_on(self, stream) -> None:
        stream.foreach_batch(lambda batch, _time: self.step(batch))


class MultiHostTenantModel:
    """App-level tenant fleet (r16, ISSUE 13 / PR 7 REMAINING b): the
    multi-tenant plane behind per-host sharded intake on a REAL process
    group — ``--tenants M`` + ``--coordinator`` was rejected before this.

    Topology: the 1D process-aligned ('data',) mesh the app-level
    multi-host flow already builds (tenant axis unsharded — every host
    holds the whole [M, F+4] stack, replicated like the single-model
    weights). Each host routes ITS OWN rows into the M-tenant split
    (deterministic key — identical routing on every host), stacks them
    locally, and assembles the global [M, B_global, ...] tenant wire with
    ``make_array_from_process_local_data`` on the row axis (axis 1 of
    the [M, ...] leaves), so no new collective. Stats come back
    [M]-stacked and psum-global; ONE pooled fetch per tick, exactly like
    single-host.

    Every host pads its tenant parts to the TOP row rung, its local
    batch's own row count (``split(..., rung=B_local)``): the hosts must
    dispatch one program shape a tick, and a rung read off each host's own
    rows would need a collective of its own to agree on (the padded wires
    have none). So a tenant here still costs the step at the whole local
    batch's rows; the single-host plane sizes it by the rows a tenant got.

    The stacked wire is the only multi-host tenant wire (the coalesced
    group buffer has no tenant-axis layout across processes). The RAGGED
    tenant split (r20, lifting the padded-only rejection) needs every
    tenant part on every host to share ONE per-shard unit capacity before
    stacking — agreed by a single allgather-max of this host's max
    per-part need (the ``[need]`` widening template: the agree collective
    rides the same once-per-batch cadence the single-model ragged wire
    already pays, zero new collectives). In the stacked assembly rows
    shard on axis 1 under ``P(None, data)``, per-shard segments land on
    their devices, and the stacked wire ships raw (the codec rides the packed one-buffer
    forms only — same rule as the single-host stacked wire). Elastic
    membership (``--elastic on``) rebuilds this wrapper in place across
    epochs via ``rebuild``, the same contract as MultiHostSGDModel."""

    accepts_packed = False  # stacked tenant wire only across processes

    def __init__(self, inner: TenantStackModel, mesh, rebuilder=None):
        self.inner = inner
        self.mesh = mesh
        self.num_data = getattr(inner, "num_data", 1)
        self._lead = jax.process_index() == 0
        self._rebuilder = rebuilder

    # tenant-plane surface the delivery chain reads (apps/common)
    @property
    def num_tenants(self) -> int:
        return self.inner.num_tenants

    @property
    def tenant_key(self) -> str:
        return self.inner.tenant_key

    @property
    def wire_pack(self) -> str:
        return "stacked"

    def route_ids(self, batch) -> np.ndarray:
        return self.inner.route_ids(batch)

    def rebuild(self, mesh) -> "MultiHostTenantModel":
        """Elastic epoch change: fresh inner stack on the new mesh, in
        place (weights restored by the caller from the lead's broadcast
        checkpoint — the PR 4 path)."""
        if self._rebuilder is None:
            raise RuntimeError(
                "MultiHostTenantModel.rebuild needs the rebuilder closure "
                "(set by apps/common.build_model)"
            )
        self.inner = self._rebuilder(mesh)
        self.mesh = self.inner.mesh  # may be None on a 1-device epoch
        self.num_data = getattr(self.inner, "num_data", 1)
        self._lead = jax.process_index() == 0
        return self

    def _to_global_stacked(self, stacked):
        from jax.sharding import NamedSharding

        from .sharding import _pspecs_for, _stacked

        data_axis = self.mesh.axis_names[0]
        specs = _stacked(_pspecs_for(type(stacked), data_axis))

        def to_global(host_arr, spec):
            host_arr = np.asarray(host_arr)
            global_shape = (
                host_arr.shape[0],
                host_arr.shape[1] * jax.process_count(),
            ) + host_arr.shape[2:]
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, spec), host_arr, global_shape
            )

        return type(stacked)(*(
            to_global(a, s) for a, s in zip(stacked, specs)
        ))

    def _stack_ragged_parts(self, parts):
        """M ragged tenant parts → ONE [M]-stacked, LOCAL-shard-aligned
        ragged batch. Stacking needs every part to share one per-shard
        unit capacity, and the fleet needs every HOST to share it too:
        one allgather-max of this host's max per-part need agrees it
        (the ``[need]`` widening template — the same once-per-batch
        collective cadence as the single-model ragged wire). Units
        harmonize to uint16 first, the pre-codec multi-host schema rule
        (a uint8 host next to a uint16 host must not fork signatures)."""
        local_shards = max(1, self.num_data // jax.process_count())
        from ..features.batch import align_ragged_shards, ragged_shard_bucket

        parts = [
            p if p.units.dtype == np.uint16 else RaggedUnitBatch(
                np.asarray(p.units, np.uint16), p.offsets, p.numeric,
                p.label, p.mask, row_len=p.row_len, num_shards=p.num_shards,
            )
            for p in parts
        ]
        need = max(ragged_shard_bucket(p, local_shards) for p in parts)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            need = int(
                multihost_utils.process_allgather(
                    np.array([need], np.int64)
                ).max()
            )
        return stack_batches([
            align_ragged_shards(p, local_shards, unit_bucket=need)
            for p in parts
        ])

    def _to_global_ragged(self, stacked):
        """[M]-stacked local-shard ragged wire → the global tenant wire:
        every leaf assembles on the ROW axis (axis 1) under ``P(None,
        data)`` — each process contributes its local shards'
        segments and the data axis hands every device its own."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(
            self.mesh, P(None, self.mesh.axis_names[0])
        )

        def to_global(host_arr):
            host_arr = np.asarray(host_arr)
            global_shape = (
                host_arr.shape[0],
                host_arr.shape[1] * jax.process_count(),
            ) + host_arr.shape[2:]
            return jax.make_array_from_process_local_data(
                sharding, host_arr, global_shape
            )

        return RaggedUnitBatch(
            *(to_global(a) for a in (
                stacked.units, stacked.offsets, stacked.numeric,
                stacked.label, stacked.mask,
            )),
            row_len=stacked.row_len, num_shards=self.num_data,
        )

    def step(self, local_batch) -> StepOutput:
        """Route + split THIS host's rows, stack, assemble the global
        tenant wire on the row axis, and run the stacked program. Dispatch
        only — the host transfer lives in ``fetch_output`` (the main
        thread never blocks on a device fetch)."""
        parts = self.inner.split(
            local_batch, rung=local_batch.mask.shape[0]
        )
        if isinstance(parts[0], RaggedUnitBatch):
            # ragged tenant wire (r20): shared-bucket aligned stack; the
            # 1-process degenerate epoch skips only the row-axis assembly
            # (the aligned stack IS the single-host placement input)
            stacked = self._stack_ragged_parts(parts)
            if jax.process_count() == 1:
                return self.inner.step(stacked)
            return self.inner.step(self._to_global_ragged(stacked))
        stacked = stack_batches(parts)
        if jax.process_count() == 1:
            # degenerate epoch (an elastic fleet shrunk to one host): the
            # inner plane's own placement path is the single-host truth
            return self.inner.step(stacked)
        return self.inner.step(self._to_global_stacked(stacked))

    def fetch_output(self, out) -> StepOutput:
        """[M]-stacked global stats for every host; the lead additionally
        localizes its own rows' [M, B_local] predictions (shards sorted by
        their ROW offset — axis 1 of the stacked output), so per-row
        telemetry stays host-local exactly like the single-model plane."""
        count, mse, real_stdev, pred_stdev, quality = jax.device_get(  # lawcheck: disable=TW002 -- fetch_output IS the counted seam: FetchPipeline installs it as _fetch, one pooled get per tick (the tenant-fleet form of MultiHostSGDModel.fetch_output)
            (out.count, out.mse, out.real_stdev, out.pred_stdev, out.quality)
        )
        preds = None
        if self._lead:
            p = out.predictions
            if p.is_fully_addressable:
                preds = np.asarray(p)
            else:
                shards = sorted(
                    p.addressable_shards,
                    key=lambda s: s.index[1].start or 0,
                )
                for s in shards:
                    s.data.copy_to_host_async()
                preds = np.concatenate(
                    [np.asarray(s.data) for s in shards], axis=1
                )
        return StepOutput(
            predictions=preds, count=count, mse=mse,
            real_stdev=real_stdev, pred_stdev=pred_stdev, quality=quality,
        )

    @property
    def latest_weights(self) -> np.ndarray:
        return self.inner.latest_weights

    def set_initial_weights(self, weights) -> "MultiHostTenantModel":
        self.inner.set_initial_weights(weights)
        return self
