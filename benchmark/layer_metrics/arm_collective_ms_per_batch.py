"""What the arms of a champion/challenger run add on the interconnect: the
device time per batch with a collective that CARRIES THE ARMS in flight, on
the chip with the most of it.

Under ``--tenantKey all --modelShards m`` (``hash2e20-grid4``) the mesh
step's collectives are ``hash2e20``'s in number; those whose payload leads
with the arm axis sit in three stages (``parallel/sharding.py``): under
``predict`` the ``u`` partials' psum over ``model`` and all-gather over
``data`` (``[M, B/d]``) and the batch statistics' sums, under ``dual_loop``
‖w_m‖² (``[M]``, before the map, never inside it), under ``writeback`` the
scale ``c`` (``[M]``) and the two deltas (``[M, F/m]``, ``[M, 4]``). This is
the union of the in-flight intervals (``benchmark/collectives.in_flight``:
an asynchronous pair counts from its start to its done) of the ``XLA Ops``
events under the ``collective`` scope whose stage — the first of the step's
nine scope names on the op-name path — is one of those three, per chip,
over the batches ``step_device_ms`` divides by. A PART of
``collective_ms_per_batch`` (which also holds the batch all-gather, the
plane gate's reductions, the panel psum and the G all-gather, none of which
knows the arms): beside it, it says whether the arms' psums went out as one
``[M, ·]`` collective each or as M, and what the 8 MiB delta psum costs.
Transfer AND waiting, as there.

None without a profile, without the ``arm_map`` scope (any other program:
``hash2e20``'s own collectives in those stages carry one model), and where
no such event ran.
"""

from benchmark import collectives, reduce_xplane, stage_times, trace_files
from benchmark.layer_metrics import arm_apply_ms_per_arm as arm_map

STAGES = ("predict", "dual_loop", "writeback")
_cache: dict = {}


def per_chip_s(planes: list) -> list:
    """``stage_times.read_xspace``'s planes → each chip's union of the
    in-flight intervals of its arm-carrying collectives, seconds."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        ops = [(s, e, m) for line in plane["lines"]
               if line["name"] == stage_times.OPS_LINE
               for s, e, m in line["events"] if e > s]
        if not ops:
            continue
        mine = [(s, e, plane["event_name"].get(m, "")) for s, e, m in ops
                if collectives.in_scope(plane["op_name"].get(m, ""))
                and stage_times.stage_of(plane["op_name"].get(m, "")) in STAGES]
        out.append(   # the trace's clock is picoseconds
            reduce_xplane.union_ns(collectives.in_flight(mine))[0] / 1e12)
    return out


def read(art):
    profile = art.get("profile")
    if not profile or not profile.get("batches"):
        return None
    if arm_map.of_live_run() is None:     # no arms in this program
        return None
    path = trace_files.xplane_file()
    if path not in _cache:
        _cache[path] = per_chip_s(stage_times.read_xspace(path))
    chips = _cache[path]
    if not chips or not max(chips):
        return None
    return 1e3 * max(chips) / profile["batches"]
