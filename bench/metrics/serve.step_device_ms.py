"""Device time per call of the stacked decode executable, from the trace."""


def read(run):
    runs, secs = run.trace.module(run.counters["step_module"])
    if not runs:
        return None
    return 1e3 * secs / runs
