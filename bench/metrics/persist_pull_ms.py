"""``persist_pull_ms`` (ms per iteration): wall time of the program's
``persist.pull`` spans, the pull of the persisted p and beta to the
host, over the iterations the window completed."""

from bench import host_phases


def read(run):
    return host_phases.span_ms(run, "persist.pull")
