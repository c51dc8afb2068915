"""Host time per served tick in the program's ``serve.h2d`` span, the
host-to-device copy of the staged batch and its mask inside
``serve.stage`` (ms): the span's self time over the traced ticks
(``benchlib.spans``).  Nothing without a trace or without the span (a
program that does not split the copy out of ``serve.stage``)."""

from benchlib import spans

SPAN = "serve.h2d"


def read(run):
    if run.trace is None:
        return None
    phases = spans.reduce(run.trace)
    if phases is None or SPAN not in phases.counts:
        return None
    return phases.ms_per_tick(SPAN)
