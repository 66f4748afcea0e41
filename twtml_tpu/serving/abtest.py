"""Champion/challenger serving on the tenant stack (ISSUE 11).

The reference's whole loop is predict-then-train on ONE model; PR 7 made M
model variants train in ONE jit program, and PR 8 gave every variant an
online quality vector. This module closes the A/B loop at serve time:

- **one program, M variants, zero added dispatches**: the engine is the PR 9
  predict-only trick on a ``TenantStackModel(num_iterations=0)``, but every
  variant sees the SAME rows — the coalesced predict batch is MIRRORED to
  all M tenants (``prepare_wire_from_parts([batch] * M)``), so challengers
  ride the champion's dispatch and fetch instead of costing their own;
- **the champion answers**: live responses select the champion tenant's row
  of the already-fetched ``[M, B]`` predictions. The champion index is
  captured at DISPATCH time and rides the device round trip with the
  output, so a batch in flight across a champion swap still answers with
  the tenant it dispatched under — the same no-torn-batch discipline as
  the snapshot hot-swap;
- **challengers are shadow-scored for free**: per-challenger divergence
  against the champion is plain host numpy over the predictions the ONE
  fetch already delivered (zero added fetches, the PR 8 pattern), and the
  authoritative online score is the PR 8 quality vector the TRAINER stamps
  per tenant into every verified checkpoint
  (``meta["quality"]["tenants"]``);
- **auto-promotion through the ONE gate**: when a new snapshot installs,
  the selector compares challengers' quality stamps against the
  champion's; a strictly better challenger is promoted by swapping the
  champion pointer — but only if ``serving.snapshot.is_promotable`` says
  its stamp may serve. An alert-stamped challenger is REFUSED and counted
  (``abtest.promotions_refused``), exactly like an alert-stamped snapshot
  at the promoter tier. Promotion fires at most once per stamped step, and
  the verdict is a pure function of the stamps — every replica of a read
  fleet converges on the same champion for the same snapshot.
"""

from __future__ import annotations

import numpy as np

from ..telemetry import metrics as _metrics
from ..utils import get_logger
from .engine import PredictEngine
from .snapshot import is_promotable

log = get_logger("serving.abtest")

# shadow divergence EWMA smoothing (host-side telemetry only)
_SHADOW_ALPHA = 0.2


def _score(entry: "dict | None") -> float:
    """The A/B ranking metric over per-tenant quality stamps — smaller is
    better: the trainer's ONLINE loss EWMA (``loss``, the PR 8 fast EWMA
    of per-tenant mse). Deliberately loss-ONLY: health never ranks here —
    whether a winner may serve is ``is_promotable``'s job, the one gate,
    so an alert-stamped challenger with the best loss is REFUSED there
    (and counted) instead of being silently out-ordered. A missing or
    invalid stamp scores worst: no evidence never promotes."""
    if not isinstance(entry, dict):
        return float("inf")
    loss = entry.get("loss", -1.0)
    try:
        loss = float(loss)
    except (TypeError, ValueError):
        loss = -1.0
    return loss if loss >= 0 else float("inf")


class ChampionSelector:
    """The champion pointer + the promotion rule. ``consider`` is called by
    the engine when a snapshot installs (serve-loop thread, between
    dispatches) and returns the new champion index, or None when nothing
    changes. Deterministic given (stamps, current champion)."""

    def __init__(self, num_tenants: int, champion: int = 0):
        if not 0 <= champion < num_tenants:
            raise ValueError(
                f"champion {champion} out of range for {num_tenants} tenants"
            )
        self.num_tenants = num_tenants
        self.champion = champion
        self._last_step: "int | None" = None
        reg = _metrics.get_registry()
        self._promotions = reg.counter("abtest.promotions")
        self._refused = reg.counter("abtest.promotions_refused")

    def consider(self, meta: "dict | None", step: int) -> "int | None":
        """One promotion decision per stamped step: gate every strictly
        better challenger through ``is_promotable`` (an alert stamp refuses
        — counted), then swap to the best survivor."""
        if self._last_step is not None and step == self._last_step:
            return None
        self._last_step = step
        quality = (meta or {}).get("quality") or {}
        tenants = quality.get("tenants") or []
        entries: dict[int, dict] = {}
        for i, e in enumerate(tenants):
            if isinstance(e, dict):
                entries[int(e.get("tenant", i))] = e
        if len(entries) < 2:
            return None  # no per-tenant stamps: nothing to compare
        best, best_entry = self.champion, entries.get(self.champion)
        for m in sorted(entries):
            if m == self.champion or not 0 <= m < self.num_tenants:
                continue
            entry = entries[m]
            if _score(entry) >= _score(best_entry):
                continue
            ok, reason = is_promotable({"finite": True, "quality": entry})
            if not ok:
                self._refused.inc()
                log.warning(
                    "challenger tenant %d REFUSED promotion at step %d "
                    "(champion stays %d): %s", m, step, self.champion,
                    reason,
                )
                continue
            best, best_entry = m, entry
        if best == self.champion:
            return None
        prev, self.champion = self.champion, best
        self._promotions.inc()
        log.info(
            "champion AUTO-promoted: tenant %d -> %d at snapshot step %d "
            "(stamp %s beats %s)", prev, best, step,
            _score(best_entry), _score(entries.get(prev)),
        )
        return best


class _ShadowTrack:
    """Rolling shadow score for one challenger: rows mirrored, EWMA of the
    mean |challenger − champion| prediction divergence."""

    __slots__ = ("rows", "divergence")

    def __init__(self):
        self.rows = 0
        self.divergence: "float | None" = None

    def observe(self, diff_mean: float, rows: int) -> None:
        self.rows += rows
        if self.divergence is None:
            self.divergence = diff_mean
        else:
            self.divergence += _SHADOW_ALPHA * (diff_mean - self.divergence)


class ChampionEngine(PredictEngine):
    """A ``PredictEngine`` over the tenant stack where live traffic is
    answered by the CHAMPION tenant and mirrored shadow-mode to every
    challenger. Drop-in for ``ServingPlane`` (same step/pack/predictions
    surface the FetchPipeline drives)."""

    def __init__(self, *args, champion: int = 0, **kwargs):
        super().__init__(*args, **kwargs)
        if self.num_tenants < 2:
            raise ValueError(
                "champion/challenger needs a tenant stack (num_tenants >= "
                f"2), got {self.num_tenants} — train with --tenants M"
            )
        self.selector = ChampionSelector(self.num_tenants, champion)
        self._recipes: "dict | None" = None  # the snapshot's own stamp
        self._shadows = [_ShadowTrack() for _ in range(self.num_tenants)]
        self._live_rows = np.zeros((self.num_tenants,), np.int64)

    @property
    def champion(self) -> int:
        return self.selector.champion

    # -- snapshot install + auto-promotion ----------------------------------
    def set_snapshot(self, snapshot) -> None:
        """Install the stack AND run the promotion rule on its per-tenant
        quality stamps — both happen on the serve-loop thread between
        dispatches (ServingPlane._install), so a swap of (weights,
        champion) is one atomic event w.r.t. dispatches."""
        super().set_snapshot(snapshot)
        meta = getattr(snapshot, "meta", None) or {}
        stamp = meta.get("tenants")
        if isinstance(stamp, dict) and len(
            stamp.get("stepSize") or ()
        ) == len(stamp.get("l2Reg") or ()) == self.num_tenants:
            # which variant is which (apps/common.tenant_stamp): the key
            # the stack was trained under and each tenant's recipe
            self._recipes = stamp
        else:
            self._recipes = None
        self.selector.consider(
            getattr(snapshot, "meta", None), int(snapshot.step)
        )

    # -- FetchPipeline surface ----------------------------------------------
    def pack_for_wire(self, batch):
        """The MIRRORED tenant wire: every variant sees the same rows —
        challengers ride the champion's coalesced batch through the one
        mapped program instead of costing their own dispatch."""
        return self.model.prepare_wire_from_parts(
            [batch] * self.num_tenants
        )

    def step(self, wire):
        """Dispatch the mirrored program; the dispatch-time champion rides
        the payload so delivery answers with the tenant this batch was
        dispatched under, even across a swap (no torn batch)."""
        return self.model.step(wire), int(self.champion)

    # -- result extraction ---------------------------------------------------
    def predictions_for(self, host_out, batch) -> np.ndarray:
        """Champion row of the fetched [M, B] predictions (mirrored wire →
        every tenant is already in original row order), plus the free
        shadow scoring pass over the challengers."""
        out, champ = host_out
        mask = np.asarray(batch.mask) > 0
        tenant_preds = np.asarray(out.predictions)
        live = tenant_preds[champ][mask]
        rows = int(mask.sum())
        self._live_rows[champ] += rows
        if rows:
            for m in range(self.num_tenants):
                if m == champ:
                    continue
                diff = float(
                    np.abs(tenant_preds[m][mask] - live).mean()
                )
                self._shadows[m].observe(diff, rows)
        return live

    def tenant_row_counts(self, batch) -> "np.ndarray | None":
        """Live-answered rows land on the champion (challengers see the
        mirror shadow-mode; their exposure is the shadow view, not served
        traffic)."""
        counts = np.zeros((self.num_tenants,), np.int64)
        counts[self.champion] = int((np.asarray(batch.mask) > 0).sum())
        return counts

    # -- telemetry -----------------------------------------------------------
    def abtest_view(self) -> dict:
        """The champion/challenger slice of the Serving view: the live
        champion plus per-tenant shadow divergence/exposure."""
        reg = _metrics.get_registry()
        shadows = []
        for m in range(self.num_tenants):
            track = self._shadows[m]
            shadows.append({
                "tenant": m,
                "live": m == self.champion,
                "liveRows": int(self._live_rows[m]),
                "shadowRows": int(track.rows),
                "divergence": round(track.divergence or 0.0, 4),
                **({} if self._recipes is None else {
                    "stepSize": float(self._recipes["stepSize"][m]),
                    "l2Reg": float(self._recipes["l2Reg"][m]),
                }),
            })
        return {
            **({} if self._recipes is None
               else {"tenantKey": str(self._recipes.get("key", ""))}),
            "champion": int(self.champion),
            "shadows": shadows,
            "promotions": int(reg.counter("abtest.promotions").snapshot()),
            "refusedPromotions": int(
                reg.counter("abtest.promotions_refused").snapshot()
            ),
        }
