"""Share of the chip's HBM bandwidth at which an arm's two contractions
with the count matrix run: the bytes they NEED over the time they took.

Per arm and batch the mapped half reads C twice, once for ``u = C·w_m``
(under ``predict``) and once for ``Cᵀα_m`` (under ``writeback``); everything
else under ``arm_map`` is ``[B]``- or ``[F]``-sized. NEEDED
(``needed_bytes``, from the configuration's own sizes): two streamed reads
of the ``[B, F]`` count matrix at the width of the plane the batch took —
4 bytes an element on the exact plane, 2 on bf16, 1 on s8 — the mean over
the span file's ``gram_plane`` instants. The ``[F]`` vectors read and
written (4 MB an arm) and the ``[B]`` ones are left out: a lower bound.
TOOK: the device time under ``arm_map`` in the ``predict`` and ``writeback``
stages a batch and arm (``arm_apply_ms_per_arm`` has the reduction), which
also holds the arm's batch stats: more time, never less. So the share cannot
pass 100%: neither contraction can read C in less than one pass over it. The
single model's write-back alone read 87% in PR 33. Batching the two
contractions over the arms (one read of C for all M) makes this count stale
— it then needs a ``benchmark`` issue to restate it, as the work counts say
of theirs. None without the scope, the instants or the live cell's sizes.
"""

from benchmark import spans, trace_files
from benchmark.layer_metrics import arm_apply_ms_per_arm as arm_map
from benchmark.layer_metrics.collective_ici_share import live_config

PLANE_BYTES = {0: 4, 1: 2, 2: 1}   # ops/gram.text_gram: exact, bf16, s8


def needed_bytes(config: dict, element_bytes: float) -> float:
    """Two reads of the ``[batch_rows, numTextFeatures]`` count matrix."""
    return (2.0 * float(config["batch_rows"])
            * float(config["model"]["numTextFeatures"]) * element_bytes)


def plane_width() -> "float | None":
    """Mean bytes an element of C over the window run's batches."""
    path = trace_files.span_file()
    if path is None:
        return None
    widths = [PLANE_BYTES[ev["args"]["plane"]]
              for ev in spans.load_events(path)
              if ev.get("ph") == "i" and ev.get("name") == "gram_plane"
              and (ev.get("args") or {}).get("plane") in PLANE_BYTES]
    return sum(widths) / len(widths) if widths else None


def read(art):
    profile, peaks = art.get("profile"), art.get("peaks")
    if not profile or not profile.get("batches") or not peaks:
        return None
    red, m, width = arm_map.of_live_run(), arm_map.arms(), plane_width()
    config = live_config()
    if red is None or not m or width is None or config is None:
        return None
    took = (red["arm_stage_s"].get("predict", 0.0)
            + red["arm_stage_s"].get("writeback", 0.0)) / profile["batches"] / m
    if took <= 0:
        return None
    floor = needed_bytes(config, width) / peaks["hbm_bytes_per_s"]
    print(f"[bench] arm_contraction_hbm_share: {floor * 1e3:.3f} ms of HBM "
          f"an arm ({width:.2f} B an element), took {took * 1e3:.3f} ms")
    return 100.0 * floor / took
