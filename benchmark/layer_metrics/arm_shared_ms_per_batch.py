"""What all the arms of a champion/challenger run pay ONCE a batch: the
device's busy time a batch less the time under the ``arm_map`` scope
(``arm_apply_ms_per_arm`` has the scope and the reduction; the two sum to
``step_device_ms``). It holds the re-pad, the hash, the plane gate, the
count matrix, G and whatever the step runs outside any scope: in
``hash2e18-grid4-trimmed-280`` it should read what ``hash2e18-trimmed-280``'s
whole step reads less that cell's own ``predict``, ``dual_loop`` and
``writeback``, and it is what a program that mapped the WHOLE step over the
arms would have paid M times. None where nothing ran under the scope."""

from benchmark.layer_metrics import arm_apply_ms_per_arm as arm_map


def read(art):
    profile = art.get("profile")
    if not profile or not profile.get("batches"):
        return None
    red = arm_map.of_live_run()
    if red is None:
        return None
    return 1e3 * (red["busy_s"] - red["arm_s"]) / profile["batches"]
