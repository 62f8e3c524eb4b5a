"""``persist_stage_ms`` (ms per iteration): wall time of the program's
``persist.begin`` spans less the ``persist.pull`` spans they enclose:
the stager copy and, for a stripe, the chunking, the parity encode and
the child stages, over the iterations the window completed."""

from bench import host_phases


def read(run):
    begin = host_phases.span_ms(run, "persist.begin")
    pull = host_phases.span_ms(run, "persist.pull")
    if begin is None or pull is None:
        return None
    return begin - pull
