"""``residual_check_ms`` (ms per iteration): wall time of the program's
``solve.residual`` spans, the convergence check at the top of every
loop pass (r.r reduced on the device, one scalar pulled, its square
root on the host), over the iterations the window completed."""

from bench import host_phases


def read(run):
    return host_phases.span_ms(run, "solve.residual")
