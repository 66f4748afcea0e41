"""What the 50 iterations of the primal learner cost on the device: the
device time under the ``primal_loop`` scope a batch.

MLlib's ``L1Updater`` (``--l1Reg``, ``lasso2e18``) thresholds every
coordinate of the weights, so its iterations cannot run in the Gram (dual)
basis: ``models/sgd.make_sgd_train_step`` runs ``sgd_inner_loop`` on the
weights themselves under ``jax.named_scope("primal_loop")``, inside the
branch of the plane taken, and every round reads the count matrix once
(``ops/gram.CountPlane.primal_pass``, scope ``primal_pass`` inside the
loop). An operation is UNDER the scope when ``primal_loop`` is a part of its
op-name path (the loop's ``while`` and everything it runs, the Pallas
kernel's custom call among them), or when it has no op-name at all and the
operation enclosing it is (a copy the compiler made inside the loop). Every
nanosecond of a device plane's ``XLA Ops`` line goes to the innermost
operation covering it (``stage_times.exclusive``), so the time under the
scope is a part of the busy time that ``step_device_ms`` divides, over the
same batches. ``primal_loop`` is not one of ``stage_times.SCOPES``: the
standing ``stage_ms.*`` readers report its time under ``stage_ms.other``.

A program without the scope (any other cell's, the parent's) gives None.
This file holds the reduction the two ``primal_*`` trace readers share
(``reduce``, ``of_live_run``) and the ``primal`` instants of the span file
the two counter readers share (``instants``).
"""

from benchmark import spans, stage_times, trace_files

SCOPE = "primal_loop"
_cache: dict = {}


def under_scope(ops: list, op_name: dict) -> list:
    """``[(start, end, metadata id)]`` of one ``XLA Ops`` line →
    ``[(start, end, under the scope?)]``."""
    out, stack = [], []   # stack: [end, under?] of the enclosing operations
    for start, end, meta in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        name = op_name.get(meta, "")
        if name:
            under = SCOPE in name.rstrip(":").split("/")
        else:
            under = bool(stack and stack[-1][1])
        out.append((start, end, under))
        stack.append([end, under])
    return out


def reduce(path: str) -> "dict | None":
    """Seconds per chip of one ``.xplane.pb``: ``busy_s`` and ``loop_s``
    (under the scope). None when no device plane ran anything."""
    credit = {True: 0, False: 0}
    chips = 0
    for plane in stage_times.read_xspace(path):
        if not plane["name"].startswith("/device:"):
            continue
        events = [ev for line in plane["lines"]
                  if line["name"] == stage_times.OPS_LINE
                  for ev in under_scope(line["events"], plane["op_name"])]
        if not events:
            continue
        chips += 1
        for under, ps in stage_times.exclusive(events)[0].items():
            credit[under] += ps
    if not chips:
        return None
    return {"busy_s": sum(credit.values()) / chips / 1e12,
            "loop_s": credit[True] / chips / 1e12}


def of_live_run() -> "dict | None":
    """The reduction of the live run's profile, made once per process;
    None without a profile or where nothing ran under the scope."""
    path = trace_files.xplane_file()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = reduce(path)
    red = _cache[path]
    return red if red and red["loop_s"] > 0 else None


def instants() -> list:
    """The ``args`` of the window run's ``primal`` instants (one a
    delivered batch, ``apps/common.attach_pipeline``): ``batch``,
    ``iterations``, ``zero_weights``, ``plane``. Read from the span file
    itself, as ``gram_fast_plane_share`` reads its instants: every batch of
    the window run, its warm-up pass included."""
    path = trace_files.span_file()
    if path is None:
        return []
    return [ev["args"] for ev in spans.load_events(path)
            if ev.get("ph") == "i" and ev.get("name") == "primal"
            and "iterations" in (ev.get("args") or {})]


def read(art):
    profile = art.get("profile")
    if not profile or not profile.get("batches"):
        return None
    red = of_live_run()
    if red is None:
        return None
    return 1e3 * red["loop_s"] / profile["batches"]
