"""Host time per served tick: the harness's ``tick`` span less the
device-busy time inside it, mean over the traced ticks (ms)."""


def read(run):
    return run.reduced.host_ms_per_tick
