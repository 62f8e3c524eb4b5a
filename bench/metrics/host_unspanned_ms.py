"""``host_unspanned_ms`` (ms per iteration): the traced window less the
union of the program's spans as the profiler's host plane recorded
them (each span is also a profiler annotation of the same name), over
the iterations the window completed.  What no span names: the solve's
set-up inside the window, waits on the step outside a pull, the loop's
own bookkeeping.  None where no program span reached the host plane,
so a broken shared clock reads as a missing value."""

from bench import host_phases


def read(run):
    if not host_phases.on_chip(run):
        return None
    names = {r["name"] for r in run.records if r["type"] == "span"}
    lo, hi = run.trace.window
    spans = sorted((max(s, lo), min(e, hi)) for s, e, name in run.trace.host
                   if name in names and e > lo and s < hi)
    if not spans:
        return None
    covered, end = 0.0, lo
    for s, e in spans:
        if e > end:
            covered += e - max(s, end)
            end = e
    return (hi - lo - covered) * 1e-6 / run.iterations
