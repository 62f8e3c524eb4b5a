"""``step_device_ms`` (ms): device time of one execution of the jitted
PCG step, the program ``jit_step`` that ``core/pcg.make_step`` builds."""

STEP_PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace.program_time(STEP_PROGRAM)
    if count == 0:
        return None
    return 1e3 * secs / count
