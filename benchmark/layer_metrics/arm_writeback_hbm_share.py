"""Share of the chip's HBM bandwidth at which the write-back of ALL the arms
of a champion/challenger run on a mesh reads its count matrix: the bytes it
NEEDS over the time the ``writeback`` stage took.

Under ``--tenantKey all --modelShards m`` (``hash2e20-grid4``) the
write-back ``Cᵀ·[α_1…α_M]`` is ONE pass over this chip's row panel of its
slice's count matrix, whatever M (``models/sgd.arms_dual_half``:
``CountPlane.tdot`` on ``[M, rows]``, M sibling reductions the compiler
fuses into one read). NEEDED (``needed_bytes``, from the configuration's own
sizes): one streamed read of that panel, ``(B/d)·(F/m)`` elements on the
``(d, m)`` mesh, at the width of the plane the batch took — 4 bytes an
element on the exact plane, 2 on bf16, 1 on s8, the mean over the span
file's ``gram_plane`` instants. The ``[M, F/m]`` vectors read and written
(16 MiB at M = 4) and the ``[M, B/d]`` ones are left out: a lower bound.
TOOK: the device time in the ``writeback`` stage a batch, the mean over the
chips (``stage_ms.writeback``'s own figure), which also holds the write-back
psum over ``data``, the scale's psum and the M scale-and-adds: more time,
never less. So the share cannot pass 100%: no write-back can read the panel
in less than one pass over it. ``hash2e20``'s single model reads ~91%
(1.311 ms needed of its 1.44 ms stage: ledger, PR 51); a write-back that
read the panel once an ARM would read ~25% at M = 4.

None without the ``arm_map`` scope (any other program), the instants, a
``--modelShards`` flag in the live cell's configuration, or a profile.
"""

from benchmark import stage_times
from benchmark.layer_metrics import arm_apply_ms_per_arm as arm_map
from benchmark.layer_metrics.arm_contraction_hbm_share import plane_width
from benchmark.layer_metrics.collective_ici_share import live_config


def needed_bytes(config: dict, element_bytes: float) -> "float | None":
    """One read of this chip's ``[B/d, F/m]`` row panel of the count matrix
    on the configuration's ``(d, m)`` mesh; None without ``--modelShards``."""
    flags = list(config.get("flags") or [])
    if "--modelShards" not in flags:
        return None
    m = int(flags[flags.index("--modelShards") + 1])
    d = int(config["chips"]) // m
    return (float(config["batch_rows"]) / d
            * float(config["model"]["numTextFeatures"]) / m * element_bytes)


def read(art):
    profile, peaks = art.get("profile"), art.get("peaks")
    if not profile or not profile.get("batches") or not peaks:
        return None
    if arm_map.of_live_run() is None:     # no arms in this program
        return None
    red, width, config = stage_times.of_live_run(), plane_width(), live_config()
    if red is None or width is None or config is None:
        return None
    nbytes = needed_bytes(config, width)
    took = red["stage_s"]["writeback"] / profile["batches"]
    if not nbytes or took <= 0:
        return None
    floor = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[bench] arm_writeback_hbm_share: {floor * 1e3:.3f} ms of HBM a "
          f"batch for all arms ({width:.2f} B an element), took "
          f"{took * 1e3:.3f} ms")
    return 100.0 * floor / took
