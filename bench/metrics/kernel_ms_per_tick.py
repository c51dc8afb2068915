"""Device time of the step megakernel (``smbgd_step_bank``) per served tick
(ms), summed over its events in the trace.  Nothing when no event of it is
found."""

KERNEL = r"^smbgd_step_bank(\.\d+)?$"


def read(run):
    return run.op_ms_per_tick(KERNEL)
