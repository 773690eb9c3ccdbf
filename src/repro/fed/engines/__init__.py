"""Client-phase execution engines for the federated round loop.

The paper's Algorithm 1 runs the selected cohort's client work (local
distillation, local fine-tuning, public-set inference + adaptive Top-k
upload) independently per client — embarrassingly parallel across the
cohort.  Interchangeable engines execute that phase:

* :class:`SequentialEngine` — the reference implementation: a Python loop
  over clients, one jitted step per client (the seed repo's behaviour).
* :class:`BatchedEngine` — keeps the fleet's LoRA/optimizer state in a
  :class:`repro.fed.store.FleetStore` and runs every phase as a single
  ``jax.vmap``-ed, ``jax.jit``-compiled, donated-buffer step over a leading
  client axis: host dispatches per round drop from O(C·steps) to O(steps),
  and the client axis is the handle accelerator backends parallelise over.
* :class:`FusedEngine` — collapses the batched engine's per-phase calls
  into ONE donated, jitted round body; the client axis can optionally be
  placed over devices with ``jax.shard_map``
  (``shard_clients=True``).
* :class:`FusedE2EEngine` — the whole round (client AND server phase) as
  one compiled call, sparse wire across the boundary, plus the
  multi-round ``lax.scan`` driver.
* :class:`HeteroClientEngine` / :class:`HeteroFusedE2EEngine` — the
  family-bucketed versions of the above for heterogeneous fleets.

All engines are driven by :func:`repro.fed.rounds.run_federated`.
Sequential and batched are bit-compatible under the same seed; the fused
engines are tolerance-compatible: identical per-client adaptive ``k`` and
ledger bytes (the budget math is the same host-side scalar code), while
accuracies/logits may drift by float round-off.  Batches are drawn through
the same per-client RNG streams in every engine.

Fleet-state residency is the engines' ``fleet_store`` knob (PR 9): the
default ``"device"`` store keeps the fleet stacked on-device exactly as
before the refactor; ``"host"`` keeps the fleet in host memory (optionally
npz-spilled) and streams only each round's cohort to the device, with a
prefetch hook overlapping the next cohort's transfer with the current
round's compute — see :mod:`repro.fed.store`.

Straggler semantics (all engines): a client whose channel state yields
``k == 0`` transmits nothing — it contributes zero uplink bytes and is
excluded from the aggregation stack entirely rather than zero-padded in.
"""

from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.fed.client import Client
from repro.fed.engines.base import (
    BroadcastState,
    ClientPhase,
    RoundsTrajectory,
    SequentialEngine,
    _channel_scan_ops,
    _ServerOwnerMixin,
    check_unique_cohort,
    cohort_budgets,
    fake_quant_dense,
    k_cap_bucket,
    shared_frozen_backbone,
    tree_stack,
)
from repro.fed.engines.batched import BatchedEngine
from repro.fed.engines.e2e import FusedE2EEngine
from repro.fed.engines.fused import FusedEngine
from repro.fed.engines.hetero import HeteroClientEngine, HeteroFusedE2EEngine

__all__ = [
    "BroadcastState",
    "ClientPhase",
    "RoundsTrajectory",
    "SequentialEngine",
    "BatchedEngine",
    "FusedEngine",
    "FusedE2EEngine",
    "HeteroClientEngine",
    "HeteroFusedE2EEngine",
    "make_engine",
    "tree_stack",
    "k_cap_bucket",
    "cohort_budgets",
    "check_unique_cohort",
]

# referenced via the package for the engine.py shim's star-import era callers
_PRIVATE_REEXPORTS = (_ServerOwnerMixin, _channel_scan_ops, fake_quant_dense,
                      shared_frozen_backbone)


def make_engine(kind: str, clients: list[Client], cfg: ModelConfig, **kwargs):
    """Build a round engine.  A fleet whose clients run more than one
    :class:`ModelConfig` (``client.cfg`` differs) is served by the
    family-bucketed heterogeneous engines for every fast ``kind`` — same
    interface, per-bucket executables — while ``sequential`` handles mixed
    fleets natively (each client runs its own architecture)."""
    if kind != "fused_e2e":
        for e2e_only in ("server", "server_distill_steps", "aggregation"):
            kwargs.pop(e2e_only, None)
    if kind == "sequential":
        if kwargs.get("quantize_wire"):
            raise NotImplementedError(
                "quantize_wire is not supported by the sequential reference"
                " engine — use 'batched', 'fused' or 'fused_e2e'"
            )
        if kwargs.get("compute_dtype", "float32") != "float32":
            raise NotImplementedError(
                "compute_dtype is not supported by the sequential reference"
                " engine — use 'fused' or 'fused_e2e'"
            )
        store = kwargs.get("fleet_store", "device")
        if store != "device" and getattr(store, "kind", store) != "device":
            raise NotImplementedError(
                "fleet_store='host' is not supported by the sequential"
                " reference engine (it keeps per-client state inside the"
                " Client objects) — use 'batched', 'fused' or 'fused_e2e'"
            )
        return SequentialEngine(
            clients, cfg,
            value_bits=kwargs.get("value_bits", 16), k_min=kwargs.get("k_min", 1),
        )
    hetero = len({c.cfg for c in clients}) > 1
    if kind == "batched":
        kwargs.pop("shard_clients", None)
        kwargs.pop("use_kernels", None)
        # the batched engine is the fp32 per-phase reference; the bf16 round
        # body exists only on the fused single-executable paths
        kwargs.pop("compute_dtype", None)
        if hetero:
            return HeteroClientEngine(kind, clients, **kwargs)
        return BatchedEngine(clients, cfg, **kwargs)
    if kind == "fused":
        if hetero:
            return HeteroClientEngine(kind, clients, **kwargs)
        return FusedEngine(clients, cfg, **kwargs)
    if kind == "fused_e2e":
        if hetero:
            return HeteroFusedE2EEngine(clients, **kwargs)
        return FusedE2EEngine(clients, cfg, **kwargs)
    raise ValueError(
        f"unknown engine: {kind!r} (expected 'sequential', 'batched', 'fused'"
        " or 'fused_e2e')"
    )
