"""Device time per call of the decode step spent in operations that the
step's HLO assigns to the ``mla`` scope (``bench/scopes.py``)."""

from bench import scopes


def read(run):
    got = scopes.step_seconds(run, "mla")
    if got is None:
        return None
    runs, secs = got
    return 1e3 * secs / runs
