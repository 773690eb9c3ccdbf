"""The least bytes the window's decode steps move in the ``moe`` part
(``bench/flops_mla.py``: counter ``moe_bytes``) over its device time and
the HBM peak; that time is the window's step count times the ``moe``
operations' traced time per call (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    got = scopes.step_seconds(run, "moe")
    if got is None or "moe_bytes" not in run.counters:
        return None
    runs, secs = got
    if not secs:
        return None
    time = secs / runs * run.counters["steps"]
    return 100.0 * run.counters["moe_bytes"] / (time * run.peaks["hbm_bytes_per_s"])
