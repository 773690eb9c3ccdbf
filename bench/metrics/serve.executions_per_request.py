"""Device executions of the session's executables (decode step, adapter
page-in) that a request's batch runs, from the trace: the prompt's and the
output's tokens, one decode step each, plus the batch's page-ins."""


def read(run):
    runs = sum(run.trace.module(name)[0] for name in run.counters["session_modules"])
    if not runs or not run.counters["batches"]:
        return None
    return runs / run.counters["batches"]
