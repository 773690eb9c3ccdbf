"""The least bytes the decode steps move (``bench.flops.decode_step_bytes``)
over their device time and the HBM peak; the device time of the window's
steps is their count times the mean time of a traced call."""


def read(run):
    runs, secs = run.trace.module(run.counters["step_module"])
    if not runs:
        return None
    step_time = secs / runs * run.counters["steps"]
    return 100.0 * run.counters["step_bytes"] / (step_time * run.peaks["hbm_bytes_per_s"])
