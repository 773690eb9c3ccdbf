"""Adapter-cache misses over lookups of distinct tenants in a batch
(``AdapterCache.stats``), over the traced window."""


def read(run):
    looked = run.counters["hits"] + run.counters["misses"]
    if not looked:
        return None
    return 100.0 * run.counters["misses"] / looked
