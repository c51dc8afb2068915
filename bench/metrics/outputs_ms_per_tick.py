"""Host time per served tick in the program's ``serve.outputs`` span, the
per-session output slices after the step (ms): the span's self time over
the traced ticks (``benchlib.spans``).  Nothing when the trace holds no
``serve.*`` span."""


def read(run):
    got = run.phase_metrics
    return None if got is None else got["outputs_ms_per_tick"]
