"""Machine-checked schedule-feasibility certificates.

`core.timeslot.evaluate` folds every constraint residual into a single
`max_violation` scalar — enough to report feasibility, not enough to
say *which* constraint drifted or to certify a third-party schedule
family constraint-by-constraint.  This module keeps each family's
worst residual separate, producing a `Certificate` that the LP fast
path, every baseline policy (core.policies), and the test suites all
share: "policy X is 1.4x
worse than optimal" is then backed by the same machine-checked
feasibility evidence as the LP numbers it is compared against.

Families (all residuals in Gbits; a schedule is feasible iff every one
is <= tol):

  capacity       eq. (28)   psi[e,w,t] <= C_uvw * D
  egress         eq. (26)   per-server egress <= rho * D
  ingress        eq. (27)   per-switch ingress <= sigma * D
  mask           eq. (46)   no traffic on flow-inadmissible edges
  conservation   eq. (25)   per-wavelength at passive vertices,
                            wavelength-summed at electronic ones
  demand         eq. (30)   |served_f - size_f|
  release        extension  no traffic before release_slot[f]
  wavelength     eq. (47)   one TX wavelength per server per slot (PON3)

The residuals are written once, in `core.timeslot.residuals`, and
`evaluate`'s `max_violation` is their max, so certifying a schedule can
never disagree with the metrics the sweeps report.  Each call reads its
tensor itself (`timeslot.schedule_coo`): a certificate is never taken
from an earlier evaluation.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .. import trace
from .timeslot import FAMILIES, ScheduleProblem, residuals, schedule_coo

# matches evaluate()'s feasibility threshold (Metrics.feasible)
FEASIBILITY_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Certificate:
    """Per-constraint-family worst residuals of one schedule tensor."""

    residuals: dict[str, float]   # family -> worst residual, Gbits
    tol: float

    @property
    def max_residual(self) -> float:
        """The largest residual, at least 0; NaN if any is NaN."""
        return float(np.max(list(self.residuals.values()), initial=0.0))

    @property
    def worst(self) -> str:
        if not self.residuals:
            return "none"
        return max(self.residuals, key=self.residuals.get)

    @property
    def ok(self) -> bool:
        return self.max_residual <= self.tol

    def summary(self) -> str:
        body = " ".join(f"{k}={self.residuals[k]:.3g}" for k in FAMILIES
                        if k in self.residuals)
        verdict = "ok" if self.ok else f"VIOLATED({self.worst})"
        return f"{verdict} tol={self.tol:g} {body}"

    def assert_ok(self, context: str = "") -> "Certificate":
        """Raise AssertionError naming the violated family; returns self
        so call sites can chain (`cert = check_schedule(...).assert_ok()`)."""
        if not self.ok:
            where = f" [{context}]" if context else ""
            raise AssertionError(
                f"infeasible schedule{where}: {self.worst} residual "
                f"{self.max_residual:.6g} > tol {self.tol:g} "
                f"({self.summary()})")
        return self


@trace.spanned("verify.check")
def check_schedule(p: ScheduleProblem, x: np.ndarray, *,
                   tol: float = FEASIBILITY_TOL) -> Certificate:
    """Certify a schedule tensor against eqs. (25)-(28), (30), (46),
    (47) and release times.  Pure numpy, deterministic; the residuals
    are `core.timeslot.residuals` of the tensor's own read, family by
    family."""
    return Certificate(residuals=residuals(p, schedule_coo(p, x)), tol=tol)
