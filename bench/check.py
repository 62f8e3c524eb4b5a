"""The comparison that decides ``correct``: what the window's solve
produced, against the plain reference and the campaign it was given.

Each number has a limit of its own; a run is correct when every number
is at or under its limit.

- ``x_err``: ``||x - x_ref|| / ||x_ref||``, the solve's iterate against
  the uninterrupted reference solve of the same iterations.  Exact state
  reconstruction promises the failure-free trajectory, so a recovery,
  a persisted payload or a step that is wrong shows here.
- ``relres_gap``: the gap between the relative residual the solve
  reports and the true one of its own ``x``, ``||b - A x|| / ||b||``
  with the reference stencil.
- ``persist_err``: the last durable pair of persisted search directions,
  read back from the store after the window, against the reference's
  ``p`` at those iterations (the persistence round trip, and in an
  erasure stripe with a lost child the degraded decode).
- ``iterations``, ``recovered``, ``storage_losses``: the report's counts
  against what the campaign scheduled; exact.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


@dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def rel_err(a: np.ndarray, ref: np.ndarray) -> float:
    a = np.asarray(a, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def durable_pair(iterations: int, period: int, history: int = 2) -> List[int]:
    """The iterations of the last complete run of ``history``
    consecutive persisted iterations at or before ``iterations``, under
    ESRP bursts of ``history`` every ``period`` (every iteration for
    ``period`` 1)."""
    def persisted(k):
        return period <= 1 or k % period < history

    k = iterations
    while not all(persisted(k - j) for j in range(history)):
        k -= 1
    return list(range(k - history + 1, k + 1))


def compare(limits: Dict[str, float], *, x, x_ref, true_relres: float,
            reported_relres: float, persisted: Dict[int, np.ndarray],
            p_ref: Dict[int, np.ndarray], iterations: int,
            planned_iterations: int, recovered: int, planned_recoveries: int,
            storage_losses: int, planned_storage_losses: int
            ) -> List[Check]:
    persist = max((rel_err(persisted[k], p_ref[k]) for k in p_ref),
                  default=float("inf")) if persisted else float("inf")
    return [
        Check("x_err", rel_err(x, x_ref), limits["x_err"]),
        Check("relres_gap", abs(reported_relres - true_relres),
              limits["relres_gap"]),
        Check("persist_err", persist, limits["persist_err"]),
        Check("iterations", abs(iterations - planned_iterations), 0),
        Check("recovered", abs(recovered - planned_recoveries), 0),
        Check("storage_losses",
              abs(storage_losses - planned_storage_losses), 0),
    ]


def as_dict(checks: Sequence[Check]) -> Dict[str, Dict[str, float]]:
    """Each number with its limit; a number that could not be read
    (not finite) is null."""
    return {c.name: {"value": c.value if np.isfinite(c.value) else None,
                     "limit": c.limit} for c in checks}
