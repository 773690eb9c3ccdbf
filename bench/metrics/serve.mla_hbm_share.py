"""The least bytes the window's decode steps move in the ``mla`` part
(``bench/flops_mla.py``: counter ``mla_bytes``) over its device time and
the HBM peak; that time is the window's step count times the ``mla``
operations' traced time per call (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    got = scopes.step_seconds(run, "mla")
    if got is None or "mla_bytes" not in run.counters:
        return None
    runs, secs = got
    if not secs:
        return None
    time = secs / runs * run.counters["steps"]
    return 100.0 * run.counters["mla_bytes"] / (time * run.peaks["hbm_bytes_per_s"])
