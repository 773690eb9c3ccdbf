"""Faults planted in the program underneath a run, to show that the check
catches them (the benchmark's tests, and its calibration on the chip).

* ``unchanged``: the decode step returns its cache as it went in;
* ``altered_token``: a served token is altered where it is produced, as
  one vocabulary id wins every step.
"""

from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "altered_token")


@contextlib.contextmanager
def planted(fault: str):
    """Within the block, the serving program runs with ``fault``."""
    if fault == "unchanged":
        from repro.serve import session as target

        name, make = "make_stacked_decode_step", target.make_stacked_decode_step

        def patched(cfg, **kwargs):
            step = make(cfg, **kwargs)

            def unchanged(frozen, slab, idx, cache, token):
                return step(frozen, slab, idx, cache, token)[0], cache

            return unchanged
    elif fault == "altered_token":
        from repro.serve import session

        target, name = session.ServeSession, "step"
        make = target.step

        def patched(self, tokens):
            return make(self, tokens).at[:, 7].add(100.0)
    else:
        raise ValueError(f"no fault {fault!r}")
    setattr(target, name, patched)
    try:
        yield
    finally:
        setattr(target, name, make)
