"""``step_hbm_roofline`` (%): the least time one PCG iteration could
take at the chip's HBM bandwidth, over the step's device time.  Both
are one chip's: the step's time is per device, and its least bytes are
those of the ``n / chips`` unknowns each chip holds."""

from bench import costs

STEP_PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace.program_time(STEP_PROGRAM)
    if count == 0 or secs <= 0:
        return None
    least = costs.pcg_step_least_bytes(run.n // run.chips, run.itemsize)
    bound_s = least / costs.chip_peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * bound_s / (secs / count)
