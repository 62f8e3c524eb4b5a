"""``residual_check_ms`` (ms per iteration): wall time of the program's
``solve.residual`` spans, the pull of r and its host norm at the top of
every loop pass, over the iterations the window completed."""

from bench import host_phases


def read(run):
    return host_phases.span_ms(run, "solve.residual")
