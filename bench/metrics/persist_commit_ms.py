"""``persist_commit_ms`` (ms per iteration): wall time of the program's
``persist.commit`` spans, the schema encode, CRC and PRD put of each
staged event (for a stripe, every child's flush), over the iterations
the window completed."""

from bench import host_phases


def read(run):
    return host_phases.span_ms(run, "persist.commit")
