"""The step megakernel's share of its roofline (%): the least time one tick
of the served sessions could take on this chip (the benchmark's logical
work over the ``device_kind``'s peaks, ``benchlib.work``) over the kernel's
measured device time per tick.  Bytes bound it at every shape the cells
run.  Nothing when the trace holds no event of the kernel."""

from benchlib import work

KERNEL = r"^smbgd_step_bank(\.\d+)?$"


def read(run):
    ms = run.op_ms_per_tick(KERNEL)
    if ms is None:
        return None
    least = work.least_time_s(run.config, run.sessions_per_tick, run.device_kind)
    return 100.0 * least["seconds"] / (ms * 1e-3)
