"""AdapterCache: hot/cold LRU paging of tenant adapters over device slots.

The tenant population is O(fleet) — the federation fine-tunes one adapter
per client — but device memory holds a slab of only ``slots`` adapter rows.
:meth:`AdapterCache.lookup` maps a batch of tenant ids to slot indices:

* **hit** — the tenant's adapter already sits in a slot: zero host traffic,
  the slot index is returned and the tenant moves to most-recently-used;
* **miss** — the least-recently-used unpinned slot is evicted (a pure slot
  reassignment: adapter rows are read-only at serve time, nothing is
  written back) and the tenant's row is paged in from the
  :class:`AdapterSource` (a live ``FleetStore`` or a ``step_N.fleet``
  shard directory, see :mod:`repro.serve.export`) via ONE jitted donated
  slab write, inside a ``serve.page_in`` profiler span tagged with the
  caller's ``batch``, the ``tenant`` and the ``slot``.

Slots referenced earlier in the same batch are pinned: a lookup never
evicts an adapter the batch it is resolving still needs.  A batch with
more DISTINCT tenants than slots cannot be scheduled and raises.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Protocol, Sequence

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve.adapters import canonicalize_row, slab_init, slab_set_row

__all__ = ["AdapterSource", "CacheStats", "AdapterCache"]


class AdapterSource(Protocol):
    """Where cold adapters live (host memory, npz shards, a FleetStore)."""

    num_adapters: int

    def lora_row(self, cid: int) -> Any:
        """Tenant ``cid``'s LoRA row tree (host or device leaves)."""
        ...


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    lookups: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class AdapterCache:
    """LRU tenant-adapter cache over a device slab of ``slots`` rows.

    ``like`` is the adapter-row skeleton (``repro.lora.lora_template`` of
    the served model's params); every paged row is validated against it.
    """

    def __init__(self, source: AdapterSource, *, like: Any, slots: int):
        if slots < 1:
            raise ValueError(f"AdapterCache needs >= 1 slot, got {slots}")
        self.source = source
        self.slots = int(slots)
        self._like = like
        self.slab = slab_init(like, self.slots)
        self._slot_of: OrderedDict[int, int] = OrderedDict()  # cid -> slot, LRU order
        self._free = list(range(self.slots))
        self.stats = CacheStats()
        # one compiled write executable for every (slot, tenant): the slab is
        # donated (in-place page-in) and the slot index is traced data
        self._write = jax.jit(slab_set_row, donate_argnums=(0,))

    # -- introspection --------------------------------------------------
    def resident(self) -> tuple[int, ...]:
        """Tenant ids currently in slots, LRU -> MRU order."""
        return tuple(self._slot_of)

    def reset_stats(self) -> None:
        self.stats = CacheStats()

    # -- the serving read path ------------------------------------------
    def _page_in(self, cid: int, pinned: set[int], batch: int) -> int:
        if self._free:
            slot = self._free.pop()
        else:
            victim = next(
                (c for c in self._slot_of if c not in pinned), None
            )
            if victim is None:  # unreachable: distinct-id count checked first
                raise RuntimeError("all slots pinned by the current batch")
            slot = self._slot_of.pop(victim)
            self.stats.evictions += 1
        with TraceAnnotation("serve.page_in", batch=batch, tenant=cid, slot=slot):
            row = canonicalize_row(self.source.lora_row(cid), self._like)
            self.slab = self._write(self.slab, row, np.int32(slot))
        self._slot_of[cid] = slot
        return slot

    def lookup(self, ids: Sequence[int], *, batch: int = 0) -> np.ndarray:
        """Slot index per request: ``ids (B,)`` tenant ids -> ``(B,) int32``
        slab slots, paging misses in from the source.  Duplicate ids within
        a batch share a slot (the first occurrence decides hit vs miss).
        ``batch`` tags the page-in spans with the caller's batch."""
        ids = [int(i) for i in ids]
        distinct = len(set(ids))
        if distinct > self.slots:
            raise ValueError(
                f"batch needs {distinct} distinct adapters but the cache "
                f"has {self.slots} slots — raise ServeConfig.slots or "
                "shrink the batch"
            )
        self.stats.lookups += 1
        pinned: set[int] = set()
        out = np.empty(len(ids), np.int32)
        for b, cid in enumerate(ids):
            if cid in self._slot_of:
                if cid not in pinned:  # duplicates count once per batch
                    self.stats.hits += 1
                self._slot_of.move_to_end(cid)
                out[b] = self._slot_of[cid]
            else:
                self.stats.misses += 1
                out[b] = self._page_in(cid, pinned, batch)
            pinned.add(cid)
        return out
