"""Device operations per served tick in the traced window, averaged over
the chips: every eager dispatch the tick loop makes shows here."""


def read(run):
    return run.reduced.ops_per_tick
