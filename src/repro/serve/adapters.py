"""Adapter slab: per-tenant LoRA rows stacked on device, gathered per request.

The serving memory model (ROADMAP "Personalized-adapter serving at fleet
scale"):

* ONE frozen backbone lives on device, shared by every tenant;
* a **slab** holds ``slots`` adapter rows stacked along a new leading axis —
  slot s of every leaf is tenant s's LoRA tree, in the exact
  :func:`repro.lora.split_lora` structure (None at frozen positions);
* a decode step receives the slab plus a per-request int32 slot index
  ``idx (B,)`` and gathers row ``idx[b]`` for request b — so one compiled
  executable serves a mixed batch of tenants.

Axis discipline: a client's LoRA row stores ``stack/posJ/lora/...`` leaves
stacked over layer REPEATS, ``(repeats, d, r)``; the decode ``fori_loop``
(transformer.stack_apply) slices axis 0 per repeat.  A slab gather yields
``(B, repeats, ...)`` — :func:`gather_adapters` therefore moves the batch
axis INSIDE the repeats axis for stack subtrees (``(repeats, B, ...)``) so
the per-repeat slice hands the attention LoRA a ``(B, d, r)`` batched
adapter, while top-level ``lora_head`` leaves stay ``(B, d, r)``.  The
batched contraction for row b is the same einsum over the same operands as
the single-adapter path (models/attention._lora_delta, models/model
._lm_logits), so stacked multi-tenant decode is bit-identical to serving
each request alone with its own adapter.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.lora import path_strings

__all__ = [
    "slab_init",
    "slab_set_row",
    "gather_adapters",
    "canonicalize_row",
]


def _is_stack_path(path) -> bool:
    """Leaves of a layer stack (``stack``, and the leading dense layers'
    ``prefix``) carry a leading repeats axis."""
    return not {"stack", "prefix"}.isdisjoint(path_strings(path))


def slab_init(like: Any, slots: int) -> Any:
    """Zeroed adapter slab: every leaf of ``like`` (an adapter-row tree or
    ShapeDtypeStruct skeleton, split_lora structure) gains a leading
    ``(slots,)`` axis."""
    return jax.tree.map(
        lambda x: jnp.zeros((slots,) + tuple(x.shape), x.dtype), like
    )


def slab_set_row(slab: Any, row: Any, slot: jax.Array) -> Any:
    """Write one adapter row into ``slab[slot]`` (pure; the AdapterCache
    jits this with the slab donated, so a page-in updates in place and the
    executable is compiled once — ``slot`` is traced data, not a constant)."""
    return jax.tree.map(
        lambda s, r: jax.lax.dynamic_update_slice_in_dim(
            s, r[None].astype(s.dtype), slot, axis=0
        ),
        slab,
        row,
    )


def gather_adapters(slab: Any, idx: jax.Array) -> Any:
    """Per-request adapter gather: leaf rows ``idx (B,)`` out of the slab.

    Returns a BATCHED adapter tree — stack-subtree leaves ``(repeats, B,
    ...)``, top-level leaves ``(B, ...)`` — ready to ``merge_lora`` into the
    shared frozen backbone for one mixed-tenant decode step.
    """

    def gather(path, leaf):
        rows = jnp.take(leaf, idx, axis=0)  # (B, ...)
        if _is_stack_path(path):
            rows = jnp.moveaxis(rows, 0, 1)  # (repeats, B, ...)
        return rows

    return jax.tree_util.tree_map_with_path(gather, slab)


def _dig(raw: Any, parts: tuple[str, ...]):
    node = raw
    for part in parts:
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def canonicalize_row(raw: Any, like: Any) -> Any:
    """Coerce a raw adapter row (e.g. the nested-dict tree a shard npz
    unflattens to, which omits frozen positions entirely) into the
    split_lora structure of ``like``, validating shapes/dtypes.  Rows that
    already have the canonical structure pass through unchanged — both are
    plain nested dicts, navigated by path."""

    def pick(path, leaf):
        parts = path_strings(path)
        val = _dig(raw, parts)
        if val is None:
            raise KeyError(
                f"adapter row is missing leaf {'/'.join(parts)!r} — the "
                "source does not match the model's LoRA structure"
            )
        if tuple(val.shape) != tuple(leaf.shape):
            raise ValueError(
                f"adapter leaf {'/'.join(parts)!r} has shape "
                f"{tuple(val.shape)}, model expects {tuple(leaf.shape)}"
            )
        return jnp.asarray(val, dtype=leaf.dtype)

    return jax.tree_util.tree_map_with_path(pick, like)
