"""JAX dispatches (``PjitFunction(...)``, ``DevicePut...``) per served tick
inside the program's ``serve.run_tick`` span, each counted once however
deep it nests (``benchlib.spans``).  Nothing when the trace holds no
``serve.*`` span."""


def read(run):
    got = run.phase_metrics
    return None if got is None else got["dispatches_per_tick"]
