"""The host phases of one traced run's iterations, read from the
program's own spans (``repro.obs.Tracer`` records, wall seconds).

The phases split the iteration that ``idle_share`` shows the chip
idling through, so they are read only from a run whose profiler trace
holds a device plane; a host-only rehearsal reads nothing, as it does
for the device readers.  Each value is milliseconds per iteration the
window completed, the denominator of ``iter_ms``.
"""
from __future__ import annotations

from typing import Optional


def on_chip(run) -> bool:
    """Whether the run's trace holds a device's timeline."""
    return run.trace is not None and run.trace.ndevices > 0 \
        and run.iterations > 0


def span_ms(run, name: str) -> Optional[float]:
    """Summed wall time of the program's ``name`` spans in milliseconds
    per window iteration, or None where the program recorded no such
    span (a program without it)."""
    if not on_chip(run):
        return None
    durs = [r["dur"] for r in run.records
            if r["type"] == "span" and r["name"] == name]
    return 1e3 * sum(durs) / run.iterations if durs else None
