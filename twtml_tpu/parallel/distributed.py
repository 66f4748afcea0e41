"""Multi-host process-group formation.

The reference's multi-node story is Spark cluster managers + Akka RPC
(README.md:40-55 `--master spark://...`; SURVEY.md §2.4). The TPU-native
equivalent is ``jax.distributed``: one Python controller per host joins a
process group over DCN, after which ``jax.devices()`` spans the pod and the
same Mesh/shard_map programs from sharding.py scale out — gradient psums ride
ICI within a slice and DCN across slices, with zero application-code change.

Stream intake is sharded by host (SURVEY.md §7 stage 5): each process runs
its own source/featurizer and contributes its rows of the global batch via
``host_local_batch_to_global``.
"""

from __future__ import annotations

import jax
import numpy as np

from ..features.batch import FeatureBatch, RaggedUnitBatch, UnitBatch
from ..utils import get_logger

log = get_logger("parallel.distributed")


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the jax.distributed process group (idempotent). With no args,
    reads the cluster env (TPU pod metadata / JAX_COORDINATOR_ADDRESS...).

    Must run before anything initializes the XLA backend (jax.distributed's
    own contract) — do NOT probe jax.process_count() first, that probe itself
    initializes the backend and forecloses pod formation."""
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        log.info(
            "joined process group: process %d/%d, %d global devices",
            jax.process_index(), jax.process_count(), jax.device_count(),
        )
    except RuntimeError as exc:
        # "already initialized" (re-entry) is fine; anything else on an
        # explicitly-requested pod is a real failure the caller must see.
        if "already" in str(exc).lower():
            log.debug("jax.distributed already initialized")
        elif coordinator_address is not None:
            raise
        else:
            log.debug("jax.distributed not initialized (%s); single-process", exc)
    except Exception as exc:  # auto-detection found no cluster env
        if coordinator_address is not None:
            raise
        log.debug("jax.distributed not initialized (%s); single-process mode", exc)


def local_rows(arr) -> np.ndarray:
    """This process's rows of a row-sharded global array, in global row
    order (shards sorted by their global offset). Per-shard device→host
    copies start async so they overlap each other; the fetch itself is
    synchronous."""
    shards = sorted(
        arr.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    for s in shards:
        s.data.copy_to_host_async()
    return np.concatenate([np.asarray(s.data) for s in shards])


def host_local_rows_to_global(arr: np.ndarray, mesh):
    """Plain per-host [B_local, ...] rows → one global row-sharded array —
    the dense-array sibling of ``host_local_batch_to_global`` (the k-means
    pipeline ships dense point matrices, not featurized batches). Requires
    the process-aligned data axis, like per-host batch intake."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    arr = np.asarray(arr)
    spec = P(mesh.axis_names[0], *([None] * (arr.ndim - 1)))
    if jax.process_count() == 1:
        return jax.device_put(arr, NamedSharding(mesh, spec))
    global_shape = (arr.shape[0] * jax.process_count(),) + arr.shape[1:]
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), arr, global_shape
    )


def _ragged_local_aligned(batch: RaggedUnitBatch, mesh) -> RaggedUnitBatch:
    """uint16-harmonized, aligned-to-LOCAL-shards ragged batch with the
    per-shard sub-buffer capacity AGREED across processes by one tiny
    allgather-max — the one alignment rule every multi-host ragged path
    shares (global assembly, per-shard packing, and group preparation), so
    every host compiles identical program shapes. Callers must invoke it at
    deterministic points (the lockstep tick / dispatch path) so the
    collective always pairs."""
    import numpy as _np

    if batch.units.dtype != _np.uint16:
        batch = RaggedUnitBatch(
            _np.asarray(batch.units, _np.uint16), batch.offsets,
            batch.numeric, batch.label, batch.mask,
            row_len=batch.row_len, num_shards=batch.num_shards,
        )
    aligned, _codec = _ragged_local_aligned_codec(batch, mesh, codec="")
    return aligned


def _ragged_local_aligned_codec(
    batch: RaggedUnitBatch, mesh, codec: str = ""
) -> "tuple[RaggedUnitBatch, int]":
    """The alignment agreement, widened for the compressed wire (r16,
    ROADMAP item 3 REMAINING): the SAME one allgather that agrees the raw
    per-shard bucket also carries this host's codec eligibility (uint8
    units) and its encoded-segment maximum, so the cross-host COMPRESSED
    bucket needs zero additional collectives. Returns ``(aligned batch,
    agreed codec bucket)`` — 0 means the wire ships raw (codec off, a
    non-ASCII host, or an incompressible agreement).

    The agreed codec bucket must cover every host's segments AFTER
    re-alignment to the agreed raw bucket, which each host cannot encode
    locally (it doesn't know the agreed raw bucket yet). The bound that
    closes the loop without a second collective: growing a segment's
    capacity only extends its trailing zero run, and the greedy digram
    encode maps 2k extra zeros to k extra zero-pair codes (dictionary
    entry 0) plus at most one boundary byte — so every host derives the
    same agreed bucket as ``max over hosts of (enc_max_h +
    ceil((agreed_raw - raw_need_h) / 2) + 1)``, rounded to the codec
    multiple, from the one gathered [need, enc_max, eligible] triple.
    ``pack_ragged_sharded`` asserts the bound at encode time (a violation
    is a codec bug, never silent wire corruption)."""
    import numpy as _np
    from jax.experimental import multihost_utils

    from ..features.batch import align_ragged_shards, ragged_shard_bucket

    num_data = mesh.shape[mesh.axis_names[0]]
    local_shards = num_data // jax.process_count()
    if not codec or codec == "off":
        if batch.num_shards == local_shards > 1:
            # already local-aligned: on the multi-host path the only
            # producer of this layout is a prior call of this function,
            # whose per-shard capacity IS the agreed bucket — skip the
            # re-allgather (a second alignment of the same batch would
            # otherwise pay one redundant DCN round trip, r5 review).
            # local_shards == 1 cannot distinguish a fresh flat
            # batch from a prepared one, so that topology keeps the
            # collective.
            return batch, 0
        need = ragged_shard_bucket(batch, local_shards)
        agreed = int(
            multihost_utils.process_allgather(
                _np.array([need], _np.int64)
            ).max()
        )
        return align_ragged_shards(batch, local_shards, unit_bucket=agreed), 0

    from ..features.wirecodec import encode, encoded_bucket

    need = ragged_shard_bucket(batch, local_shards)
    eligible = int(batch.units.dtype == _np.uint8)
    enc_max = 0
    if eligible:
        # encode at LOCAL alignment; the agreed bound formula below lifts
        # it to the agreed raw bucket without re-encoding
        local = align_ragged_shards(batch, local_shards, unit_bucket=need)
        segs = _np.asarray(local.units).reshape(local_shards, -1)
        enc_max = max(int(encode(r).shape[0]) for r in segs)
    gathered = multihost_utils.process_allgather(
        _np.array([need, enc_max, eligible], _np.int64)
    )
    gathered = _np.atleast_2d(gathered)
    agreed_raw = int(gathered[:, 0].max())
    all_eligible = bool(gathered[:, 2].min())
    aligned = align_ragged_shards(batch, local_shards, unit_bucket=agreed_raw)
    if not all_eligible:
        # mixed dtypes across hosts: harmonize to the full uint16 schema
        # (the pre-codec rule) and ship raw — counted as a codec fallback
        # at the app seam
        if aligned.units.dtype != _np.uint16:
            aligned = RaggedUnitBatch(
                _np.asarray(aligned.units, _np.uint16), aligned.offsets,
                aligned.numeric, aligned.label, aligned.mask,
                row_len=aligned.row_len, num_shards=aligned.num_shards,
            )
        return aligned, 0
    per_host = gathered[:, 1] + (agreed_raw - gathered[:, 0] + 1) // 2 + 1
    agreed_codec = encoded_bucket(int(per_host.max()))
    if agreed_codec >= agreed_raw:
        return aligned, 0  # incompressible agreement: raw is smaller
    # the codec rides the uint8 wire; all hosts agreed eligibility, so the
    # narrow dtype is consistent fleet-wide (the uint16 harmonization is
    # exactly what the eligibility gather replaces)
    if aligned.units.dtype != _np.uint8:
        aligned = RaggedUnitBatch(
            _np.asarray(aligned.units, _np.uint8), aligned.offsets,
            aligned.numeric, aligned.label, aligned.mask,
            row_len=aligned.row_len, num_shards=aligned.num_shards,
        )
    return aligned, agreed_codec


class MultiHostSGDModel:
    """Per-host sharded intake over a multi-process mesh, with the same step
    surface the apps consume (apps/common.build_model): LOCAL host batches
    in, host-relevant outputs back.

    ``step`` assembles this host's featurized rows into the global
    row-sharded batch (``host_local_batch_to_global``), runs the inner
    mesh-sharded step (whose gradient psums ride ICI within a host and DCN
    across — the treeAggregate analog, SURVEY.md §3.3), and returns a
    StepOutput whose scalar stats are GLOBAL (psum over the whole data
    axis, identical on every host) while ``predictions`` is localized to
    THIS host's contributed rows — aligned with the local batch the app's
    handler already holds, so per-row telemetry (real/pred series) stays a
    host-local concern and no host ever fetches another host's rows."""

    def __init__(self, inner, mesh, rebuilder=None):
        self.inner = inner
        self.mesh = mesh
        self.num_data = inner.num_data
        self._lead = jax.process_index() == 0
        # elastic membership (--elastic on): how to rebuild the inner
        # mesh-sharded model for a re-formed epoch's mesh — a closure over
        # the conf, set by apps/common.build_model
        self._rebuilder = rebuilder

    def rebuild(self, mesh) -> "MultiHostSGDModel":
        """Swap in a fresh inner model on a NEW epoch's mesh IN PLACE —
        every holder of this wrapper (fetch pipelines, checkpoint
        closures, the sentinel) keeps working across an elastic membership
        change. Weights start at zeros; the caller restores them from the
        lead's broadcast checkpoint (the PR 4 path) before the next tick."""
        if self._rebuilder is None:
            raise RuntimeError(
                "MultiHostSGDModel.rebuild needs the rebuilder closure "
                "(set by apps/common.build_model)"
            )
        self.inner = self._rebuilder(mesh)
        # the rebuilder may substitute a mesh (a shrunken 1-device epoch
        # gets a synthesized 1-device data mesh) — the inner's is the truth
        self.mesh = self.inner.mesh
        self.num_data = self.inner.num_data
        self._lead = jax.process_index() == 0
        return self

    @property
    def latest_weights(self):
        return self.inner.latest_weights

    def set_initial_weights(self, weights) -> "MultiHostSGDModel":
        self.inner.set_initial_weights(weights)
        return self

    # the module-level helper, kept as a method name for call sites
    _local_rows = staticmethod(local_rows)

    # the ragged wire packs per shard on multi-host too (pack_for_wire);
    # the app-side pack opt-in keys off this (apps/common.py).
    # --wireCodec dict (r16): the cross-host compressed bucket rides the
    # SAME alignment allgather the raw bucket already pays
    # (_ragged_local_aligned_codec) — zero added collectives, asserted by
    # the counted elastic acceptance test; set by apps/common.build_model.
    accepts_packed = True
    wire_codec = ""

    def step(self, local_batch):
        """Dispatch only — returns the StepOutput with predictions still
        GLOBAL (row-sharded). Localization + host transfer live in
        ``fetch_output`` so the main thread never blocks on a device
        fetch at dispatch time (the synchronous lead-side
        ``local_rows`` here re-introduced exactly the per-batch sync the
        FetchPipeline exists to remove). A PackedBatch from
        ``pack_for_wire`` is already the assembled global wire — pass it
        straight to the mesh step."""
        from ..features.batch import PackedBatch

        if isinstance(local_batch, PackedBatch):
            return self.inner.step(local_batch)
        return self.inner.step(
            host_local_batch_to_global(local_batch, self.mesh)
        )

    def pack_for_wire(self, local_batch):
        """The multi-host form of the one-buffer ragged wire: align this
        host's rows to its LOCAL shard segments (agreed bucket — uniform
        per-segment bytes on every host), pack them, and assemble the
        global per-shard buffer from every process's contribution. With
        ``wire_codec`` set, the compressed bucket is agreed on the SAME
        alignment allgather and every host packs identical codec segment
        shapes (or every host ships raw — the fallback decision is part of
        the agreement, never per-host)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..features.batch import PackedBatch, pack_ragged_sharded

        if not isinstance(local_batch, RaggedUnitBatch):
            raise TypeError(
                "pack_for_wire is the ragged wire's pack; padded batches "
                "assemble as plain arrays"
            )
        if self.wire_codec:
            aligned, codec_bucket = _ragged_local_aligned_codec(
                local_batch, self.mesh, codec=self.wire_codec
            )
            pb = pack_ragged_sharded(
                aligned, num_shards_out=self.num_data,
                codec=self.wire_codec if codec_bucket else None,
                codec_bucket=codec_bucket or None,
            )
        else:
            aligned = _ragged_local_aligned(local_batch, self.mesh)
            pb = pack_ragged_sharded(aligned, num_shards_out=self.num_data)
        sharding = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        buf = jax.make_array_from_process_local_data(
            sharding, pb.buffer,
            (pb.buffer.shape[0] * jax.process_count(),),
        )
        # the local buffer's arena lease rides to the dispatch pipeline
        # (retired once the step's fetch delivers — apps/common.py)
        return PackedBatch(buf, pb.layout)._with_lease(pb._lease)

    def fetch_output(self, out):
        """StepOutput → host numpy, the model-aware form of
        ``jax.device_get`` the fetch paths use (FetchPipeline workers and
        the wall-clock per-batch fetch): global scalars for every host,
        predictions localized to THIS host's contributed rows on the lead
        only (telemetry is lead-owned; followers skip the row fetch)."""
        from ..models.base import StepOutput

        count, mse, real_stdev, pred_stdev, quality = jax.device_get(  # lawcheck: disable=TW002 -- fetch_output IS the counted seam: FetchPipeline installs it as _fetch, one pooled get per tick (counted in tests/test_distributed_multiprocess.py)
            (out.count, out.mse, out.real_stdev, out.pred_stdev, out.quality)
        )
        return StepOutput(
            predictions=(
                self._local_rows(out.predictions) if self._lead else None
            ),
            count=count,
            mse=mse,
            real_stdev=real_stdev,
            pred_stdev=pred_stdev,
            quality=quality,
        )


def host_local_batch_to_global(
    batch: FeatureBatch | UnitBatch | RaggedUnitBatch, mesh
) -> FeatureBatch | UnitBatch | RaggedUnitBatch:
    """Assemble each host's locally-featurized rows into one global
    row-sharded batch (multi-host stream sharding), for any wire format
    (host-hashed tokens, raw code units, or the ragged wire). Single
    process: no-op beyond device placement.

    Ragged wire: each host re-lays its rows into its LOCAL data shards'
    segments (``align_ragged_shards``), with the per-shard sub-buffer
    capacity AGREED across processes by one tiny allgather-max of each
    host's requirement — the lockstep scheduler guarantees every host
    assembles on every tick, so the collective always pairs, and the
    agreed bucket keeps every host's compiled program shapes identical
    (the lockstep contract). The r3 narrow-wire harmonization applies to
    the ragged units too.

    Topology requirement: per-host intake sharding assumes the mesh's data
    axis is PROCESS-ALIGNED (each data shard's devices belong to one
    process) — the default `make_mesh` over process-major `jax.devices()`
    satisfies this. A mesh whose model axis crosses processes makes every
    host's devices hold rows of every data shard; such layouts must ship
    the full batch from each host via `shard_batch` instead (see
    tests/distributed_worker.py's 2d mode)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .sharding import _pspecs_for

    if jax.process_count() == 1:
        from .sharding import shard_batch

        return shard_batch(batch, mesh)

    def to_global(host_arr, spec):
        sharding = NamedSharding(mesh, spec)
        global_shape = (
            host_arr.shape[0] * jax.process_count(),
        ) + host_arr.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(host_arr), global_shape
        )

    if isinstance(batch, RaggedUnitBatch):
        data_axis = mesh.axis_names[0]
        num_data = mesh.shape[data_axis]
        batch = _ragged_local_aligned(batch, mesh)
        spec = P(data_axis)
        return RaggedUnitBatch(
            *(to_global(a, spec) for a in (
                batch.units, batch.offsets, batch.numeric, batch.label,
                batch.mask,
            )),
            row_len=batch.row_len,
            num_shards=num_data,
        )

    if isinstance(batch, UnitBatch) and batch.units.dtype != np.uint16:
        # the units wire dtype is per-batch metadata (uint8 iff every row
        # is ASCII, featurizer._pad_ragged_units); cross-process assembly
        # needs ONE dtype on every host, and hosts see different shards —
        # harmonize to the full uint16 schema here (multi-host intake rides
        # DCN, not the single-host transport the narrow wire optimizes)
        batch = batch._replace(units=batch.units.astype(np.uint16))
    specs = _pspecs_for(type(batch), mesh.axis_names[0])
    return type(batch)(*(
        to_global(host_arr, spec) for host_arr, spec in zip(batch, specs)
    ))
