"""Per-tenant telemetry view for the multi-tenant model plane (ISSUE 7).

Mirrors the sideband's ``Hosts`` pattern (telemetry/sideband.py →
``last_hosts`` → SessionStats, one frame a period → /api/hosts): the tenant
handle adapter (apps/common.attach_tenant_plane) records one row per tenant
per delivered tick from the ALREADY-FETCHED stacked StepOutput — pure
host-side bookkeeping, ZERO added host fetches (the r2/r3 measurement law)
— and ``last_tenants`` exposes the rolling view the dashboard's ``Tenants``
tiles render. Registry state rides along: ``tenants.active`` (tenants with
rows this tick), per-tenant ``tenant.<m>.rows`` counters, and
``tenant.<m>.mse`` gauges, all visible on /api/metrics without a dashboard.

The *gating* tenant is the one with the most rows this tick — the tenant
that binds the shared row bucket's capacity (the analog of the straggler
host: where the next capacity problem will surface first).
"""

from __future__ import annotations

import threading

import numpy as np

from . import metrics as _metrics

_lock = threading.Lock()
_state: "dict | None" = None
_recipes: "dict | None" = None


def reset_for_tests() -> None:
    global _state, _recipes
    with _lock:
        _state = None
        _recipes = None


def configure(step_sizes, l2_regs) -> None:
    """Each tenant's recipe, in tenant order (apps/common.build_model): the
    view carries them so a dashboard row says which recipe it shows (under
    ``--tenantKey all`` the tenants are arms of one learner that differ in
    nothing else)."""
    global _recipes
    with _lock:
        _recipes = {
            "stepSize": [float(v) for v in step_sizes],
            "l2Reg": [float(v) for v in l2_regs],
        }


def record_tick(counts, mses) -> None:
    """One delivered tick's per-tenant (row count, mse) — called by the
    tenant handle adapter with host-side numpy scalars."""
    global _state
    counts = np.asarray(counts, np.int64)
    mses = np.asarray(mses, np.float64)
    m = counts.shape[0]
    with _lock:
        st = _state
        if st is None or st["rows"].shape[0] != m:
            st = {
                "rows": np.zeros((m,), np.int64),
                "ticks": 0,
                "last_counts": np.zeros((m,), np.int64),
                "last_mses": np.zeros((m,), np.float64),
            }
        st["rows"] += counts
        st["ticks"] += 1
        st["last_counts"] = counts
        st["last_mses"] = mses
        _state = st
    reg = _metrics.get_registry()
    active = int((counts > 0).sum())
    reg.gauge("tenants.active").set(active)
    reg.gauge("tenants.configured").set(m)
    for i in range(m):
        if counts[i]:
            reg.counter(f"tenant.{i}.rows").inc(int(counts[i]))
            if np.isfinite(mses[i]):
                reg.gauge(f"tenant.{i}.mse").set(round(float(mses[i]), 3))


def last_tenants() -> "dict | None":
    """The dashboard view: one row per tenant (cumulative rows, last-tick
    rows/mse), the gating tenant (most rows this tick; -1 when all dry),
    and the active count. None until a tenant tick has been recorded."""
    with _lock:
        st = _state
        if st is None:
            return None
        counts = st["last_counts"]
        gating = int(np.argmax(counts)) if counts.any() else -1
        m = st["rows"].shape[0]
        rec = _recipes if _recipes and len(_recipes["stepSize"]) == m else None
        return {
            "tenants": [
                {
                    "tenant": i,
                    "rows": int(st["rows"][i]),
                    "batch": int(counts[i]),
                    "mse": (
                        round(float(st["last_mses"][i]), 3)
                        if np.isfinite(st["last_mses"][i]) else -1.0
                    ),
                    **({} if rec is None else {
                        "stepSize": rec["stepSize"][i],
                        "l2Reg": rec["l2Reg"][i],
                    }),
                }
                for i in range(m)
            ],
            "gating": gating,
            "active": int((counts > 0).sum()),
            "ticks": int(st["ticks"]),
        }
