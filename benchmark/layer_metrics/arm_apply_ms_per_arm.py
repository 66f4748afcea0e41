"""What one more challenger costs on the device: the device time under the
``arm_map`` scope a batch, over the M arms.

Under ``--tenantKey all`` (``hash2e18-grid4``) the arms of a champion/
challenger run share their rows, so ``models/sgd.make_sgd_train_step(arms=
True)`` builds the count matrix and the Gram matrix ONCE a batch and maps
only the per-arm half — ``u = C·w_m``, the dual loop, ``Cᵀα_m``, the stats —
over the arms under ``jax.named_scope("arm_map")``, inside the branch of the
plane taken. An operation is UNDER the scope when ``arm_map`` is a part of
its op-name path (the ``lax.map``'s ``while`` and everything it runs), or
when it has no op-name at all and the operation enclosing it is (a copy the
compiler made inside the loop). Every nanosecond of a device plane's ``XLA
Ops`` line goes to the innermost operation covering it
(``stage_times.exclusive``), so the time under the scope and the time
outside it (``arm_shared_ms_per_batch``) sum to the busy time that
``step_device_ms`` divides.

M is the length of ``rows`` in the span file's ``tenant_rows`` instants
whose ``key`` is ``all`` (``apps/common.attach_pipeline``'s tenant adapter).
A program without the scope or the instant (the parent's, any other cell's)
gives None. This file holds the reduction the three ``arm_*`` readers share
(``reduce``, ``under_arm_map``).
"""

from benchmark import spans, stage_times, trace_files

SCOPE = "arm_map"
_cache: dict = {}


def under_arm_map(ops: list, op_name: dict) -> list:
    """``[(start, end, metadata id)]`` of one ``XLA Ops`` line →
    ``[(start, end, (under the scope?, stage))]``. The stage is the first
    of ``stage_times.SCOPES`` on the op-name path (``stage_of``)."""
    out, stack = [], []   # stack: [end, under?] of the enclosing operations
    for start, end, meta in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        name = op_name.get(meta, "")
        if name:
            under = SCOPE in name.rstrip(":").split("/")
        else:
            under = bool(stack and stack[-1][1])
        out.append((start, end, (under, stage_times.stage_of(name))))
        stack.append([end, under])
    return out


def reduce(path: str) -> "dict | None":
    """Seconds per chip of one ``.xplane.pb``: ``busy_s``, ``arm_s`` (under
    the scope) and ``arm_stage_s`` (under the scope, by stage). None when no
    device plane ran anything."""
    credit: dict = {}
    chips = 0
    for plane in stage_times.read_xspace(path):
        if not plane["name"].startswith("/device:"):
            continue
        events = [ev for line in plane["lines"]
                  if line["name"] == stage_times.OPS_LINE
                  for ev in under_arm_map(line["events"], plane["op_name"])]
        if not events:
            continue
        chips += 1
        for label, ps in stage_times.exclusive(events)[0].items():
            credit[label] = credit.get(label, 0) + ps
    if not chips:
        return None
    arm_stage: dict = {}
    for (under, stage), ps in credit.items():
        if under:
            arm_stage[stage] = arm_stage.get(stage, 0) + ps / chips / 1e12
    return {
        "busy_s": sum(credit.values()) / chips / 1e12,
        "arm_s": sum(arm_stage.values()),
        "arm_stage_s": arm_stage,
    }


def of_live_run() -> "dict | None":
    """The reduction of the live run's profile, made once per process;
    None without a profile or where nothing ran under the scope."""
    path = trace_files.xplane_file()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = reduce(path)
    red = _cache[path]
    return red if red and red["arm_s"] > 0 else None


def arms() -> "int | None":
    """M, from the window run's span file."""
    path = trace_files.span_file()
    if path is None:
        return None
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if (ev.get("ph") == "i" and ev.get("name") == "tenant_rows"
                and a.get("key") == "all" and a.get("rows")):
            return len(a["rows"])
    return None


def read(art):
    profile = art.get("profile")
    if not profile or not profile.get("batches"):
        return None
    red, m = of_live_run(), arms()
    if red is None or not m:
        return None
    return 1e3 * red["arm_s"] / profile["batches"] / m
