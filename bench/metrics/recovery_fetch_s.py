"""``recovery_fetch_s`` (s): wall time of the program's
``recovery.fetch`` spans, summed over the window's recoveries and
divided by their count."""


def read(run):
    return run.span_per_recovery("recovery.fetch")
