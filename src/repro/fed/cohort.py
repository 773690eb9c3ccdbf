"""Family-bucket partitioning of a (possibly heterogeneous) client fleet.

The paper's central claim is that federated *distillation* — exchanging
vocab-indexed logits and rank-aligned LoRA projections instead of
parameters — lets clients with DIFFERENT architectures participate in one
federation (PAPER.md; Fig. 1's shared logit space).  The fast engines,
however, execute a cohort as ONE vmapped program over a leading client
axis, which requires every stacked client to share a parameter tree
layout.  This module is the bridge: it partitions the fleet into
homogeneous **family buckets** — maximal groups of clients running the
same :class:`~repro.configs.base.ModelConfig` — so the round engines can
run one compiled, donated client-phase executable *per bucket* and merge
the buckets' uploads in the model-agnostic logit space (the union
:class:`~repro.core.topk.SparseWire` is vocab-indexed, so an SSM bucket
and a dense bucket aggregate exactly as the paper's eqs. 6-7 prescribe).

Within a bucket the frozen backbones may still differ per client (e.g. no
shared pretrained W'): the bucket then carries its frozen trees STACKED on
the client axis (``shared_backbone=False`` -> ``frozen_ax=0`` in the
vmapped round bodies), which is the existing batched-engine contract.

The only cross-family contracts are the paper's own (§II): a shared
vocabulary (the logit exchange space) and — when the ``adald`` projection
loss is used — a shared LoRA rank r (eq. 8's h = A·x lives in R^r).
:func:`validate_family_contracts` enforces both at engine construction,
fail-fast, instead of letting a shape error surface mid-round.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.configs.base import ModelConfig
from repro.fed.client import Client

__all__ = [
    "FamilyBucket",
    "partition_fleet",
    "fleet_index",
    "split_cohort",
    "validate_family_contracts",
]


@dataclasses.dataclass(frozen=True)
class FamilyBucket:
    """One homogeneous slice of the fleet: every member runs ``cfg``.

    ``client_ids`` are GLOBAL fleet indices in fleet order; a client's
    bucket-local index is its position in this tuple.  ``shared_backbone``
    is the identity test the batched engine already uses: True iff every
    member's frozen tree is literally the same arrays (one pretrained W'
    under per-client LoRA deltas — the paper's setting); False means the
    bucket stacks its frozen trees along the client axis.
    """

    index: int
    cfg: ModelConfig
    client_ids: tuple[int, ...]
    shared_backbone: bool

    @property
    def size(self) -> int:
        return len(self.client_ids)

    def local(self, global_id: int) -> int:
        """Bucket-local index of a global fleet id."""
        return self.client_ids.index(global_id)


def partition_fleet(clients: Sequence[Client]) -> list[FamilyBucket]:
    """Group the fleet into family buckets by :class:`ModelConfig`, in order
    of first appearance (stable: a homogeneous fleet is exactly one bucket,
    and the engines built on top of this reduce to their PR-4 behaviour).

    Bucketing is by config value, not backbone identity: a same-config fleet
    with per-client random backbones stays ONE bucket with
    ``shared_backbone=False`` (stacked frozens) rather than fragmenting into
    singletons — the vmapped executable still serves it.
    """
    from repro.fed.engines.base import shared_frozen_backbone
    from repro.lora import split_lora

    order: list[ModelConfig] = []
    members: dict[ModelConfig, list[int]] = {}
    for i, c in enumerate(clients):
        if c.cfg not in members:
            order.append(c.cfg)
            members[c.cfg] = []
        members[c.cfg].append(i)

    buckets = []
    for bi, cfg in enumerate(order):
        ids = members[cfg]
        frozens = [split_lora(clients[i].params)[1] for i in ids]
        buckets.append(
            FamilyBucket(
                index=bi,
                cfg=cfg,
                client_ids=tuple(ids),
                shared_backbone=shared_frozen_backbone(frozens),
            )
        )
    return buckets


def fleet_index(
    buckets: Sequence[FamilyBucket],
) -> dict[int, tuple[int, int]]:
    """O(1) lookup table ``global fleet id -> (bucket index, bucket-local
    index)`` — the one mapping both heterogeneous engines route client
    reads through."""
    return {
        cid: (b.index, j)
        for b in buckets
        for j, cid in enumerate(b.client_ids)
    }


def split_cohort(
    buckets: Sequence[FamilyBucket], sel: Sequence[int]
) -> list[tuple[FamilyBucket, list[int], list[int]]]:
    """Partition one round's selected cohort across its family buckets.

    Returns ``(bucket, cohort_positions, local_ids)`` for every bucket with
    at least one selected client, preserving cohort order within each bucket
    (so the first selected client of a bucket is that bucket's row 0 — the
    invariant the per-family eval tap and the payload reassembly rely on).
    ``cohort_positions`` index into ``sel``; ``local_ids`` are bucket-local
    client indices.
    """
    where = {cid: b for b in buckets for cid in b.client_ids}
    parts: list[tuple[FamilyBucket, list[int], list[int]]] = []
    for b in buckets:
        pos = [p for p, cid in enumerate(sel) if where[int(cid)] is b]
        if pos:
            parts.append((b, pos, [b.local(int(sel[p])) for p in pos]))
    return parts


def validate_family_contracts(
    buckets: Sequence[FamilyBucket], *, server_cfg: ModelConfig | None = None
) -> None:
    """Enforce the paper's cross-family exchange contracts (§II):

    * one shared vocabulary — the logit space every upload/broadcast is
      indexed in (eq. 4's dimension c);
    * one shared LoRA rank (or LoRA disabled everywhere) — eq. 8's
      projection h = A·x must have a common dimensionality to be
      aggregated/distilled across families.

    ``server_cfg`` (when given) is held to the same contracts — the server's
    broadcast rides the identical spaces in the other direction.
    """
    cfgs = [b.cfg for b in buckets]
    if server_cfg is not None:
        cfgs.append(server_cfg)
    vocabs = {c.vocab_size for c in cfgs}
    if len(vocabs) > 1:
        raise ValueError(
            f"heterogeneous fleet must share one vocabulary (the logit "
            f"exchange space), got vocab sizes {sorted(vocabs)}"
        )
    ranks = {None if c.lora is None else c.lora.rank for c in cfgs}
    if len(ranks) > 1:
        raise ValueError(
            "heterogeneous fleet must share one LoRA rank for the eq.-8 "
            f"projection exchange (or disable LoRA everywhere), got {ranks}"
        )
