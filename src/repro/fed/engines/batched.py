"""The batched (vmapped per-phase) cohort engine — and the fleet-state
plumbing every fast engine inherits (store-routed since PR 9)."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.channel import BatchedChannelState, ChannelState
from repro.core.protocol import UplinkPayload
from repro.core.topk import topk_mask_batch
from repro.fed import steps as fed_steps
from repro.fed.client import Client, make_upload_payload
from repro.fed.engines.base import (
    BroadcastState,
    ClientPhase,
    check_unique_cohort,
    cohort_budgets,
    fake_quant_dense,
    shared_frozen_backbone,
)
from repro.fed.store import FleetStore, make_fleet_store
from repro.lora import merge_lora, split_lora

__all__ = ["BatchedEngine"]


class BatchedEngine:
    """Batched client-phase executor: the whole cohort advances through each
    phase as one compiled step over a leading client axis.

    The fleet's trainable state lives in a :class:`repro.fed.store.FleetStore`
    picked by ``fleet_store``: ``"device"`` (default) keeps every client's
    LoRA tree and optimizer state stacked along a leading
    ``(num_clients, ...)`` device axis exactly as before the store refactor
    (the frozen backbone is one shared tree when all clients ride the same
    pretrained W' — the paper's setting — or stacked otherwise);
    ``"host"`` keeps the fleet in host numpy and stages only the selected
    cohort onto the device per round (O(cohort) device memory, any fleet
    size).  A round fetches the selected cohort's rows from the store, runs
    the vmapped phases, and commits the advanced rows back — no per-client
    stack/unstack/merge churn on the hot path.  The engine is the source of
    truth for client parameters while it is in use; read them back through
    :meth:`client_params`.
    """

    name = "batched"

    def __init__(
        self,
        clients: list[Client],
        cfg: ModelConfig,
        *,
        num_classes: int,
        lr: float = 1e-3,
        distill_lr: float = 1e-3,
        temperature: float = 2.0,
        lam: float = 0.03,
        local_steps: int = 4,
        distill_steps: int = 2,
        restrict_to_support: bool = False,
        value_bits: int = 16,
        k_min: int = 1,
        quantize_wire: bool = False,
        fleet_store: "str | FleetStore" = "device",
    ):
        self.clients = clients
        self.cfg = cfg
        self.local_steps = local_steps
        self.distill_steps = distill_steps
        self.value_bits = value_bits
        self.k_min = k_min
        self.quantize_wire = quantize_wire

        loras, frozens = zip(*(split_lora(c.params) for c in clients))
        self._shared = shared_frozen_backbone(frozens)
        self._store = make_fleet_store(
            fleet_store, loras=loras, frozens=frozens,
            opts=[c.opt for c in clients], shared=self._shared,
        )
        self._train = fed_steps.make_batched_finetune_step(
            cfg, num_classes, lr=lr, shared_backbone=self._shared
        )
        self._distill = fed_steps.make_batched_distill_step(
            cfg, lr=distill_lr, temperature=temperature, lam=lam,
            restrict_to_support=restrict_to_support, shared_backbone=self._shared,
        )
        self._public = fed_steps.make_batched_public_logits(
            cfg, shared_backbone=self._shared
        )

    # -- fleet-state ownership: delegated to the store -------------------
    # The stacked-tree attributes stay addressable (the scan-carry drivers
    # read/donate and reassign them) but only exist on the device store;
    # the host store raises with the scan_rounds tradeoff spelled out.
    @property
    def store_kind(self) -> str:
        return self._store.kind

    @property
    def _lora(self):
        return self._store.lora

    @_lora.setter
    def _lora(self, tree):
        self._store.lora = tree

    @property
    def _opt(self):
        return self._store.opt

    @_opt.setter
    def _opt(self, tree):
        self._store.opt = tree

    @property
    def _frozen(self):
        return self._store.frozen

    @_frozen.setter
    def _frozen(self, tree):
        self._store.frozen = tree

    def client_params(self, cid: int):
        """Materialise one client's merged params (for evaluation)."""
        lora_i, frozen_i = self._store.client_row(cid)
        return merge_lora(lora_i, frozen_i)

    def fleet_state(self) -> dict:
        """The engine-held fleet state as one checkpointable pytree.  The
        frozen backbone is included so a restored run never depends on the
        construction path reproducing it (it does today, but checkpoints
        should stand alone)."""
        return self._store.state_dict()

    def load_fleet_state(self, state: dict) -> None:
        self._store.load_state_dict(state)

    def save_fleet_shards(self, dir_path: str, *, prefix: str = "fleet") -> None:
        """Persist the fleet as per-client-range shards (fleet-scale
        checkpoints: never materializes the fleet as one tree).  The hetero
        engines pass a per-bucket ``prefix`` so buckets share one dir."""
        self._store.save_shards(dir_path, prefix=prefix)

    def load_fleet_shards(self, dir_path: str, *, prefix: str = "fleet") -> None:
        self._store.load_shards(dir_path, prefix=prefix)

    # -- round plumbing shared by the batched and fused engines ----------
    def _gather_cohort(self, sel: Sequence[int]):
        """The selected cohort's (idx, lora, frozen, opt) from the store."""
        return self._store.fetch(sel)

    def _scatter_cohort(self, idx, lora, opt) -> None:
        """Write the advanced cohort rows back into the fleet state."""
        self._store.commit(idx, lora, opt)

    def prefetch_cohort(self, sel: Sequence[int]) -> None:
        """Hint the NEXT round's cohort: a host store starts staging its
        host->device transfer now, overlapping the current round's compute
        (no-op on the device store)."""
        self._store.prefetch(sel)

    def _budgets(
        self, states, n_samples: int, adaptive_k: bool, n_cohort: int,
        send_h: bool = False,
    ):
        """Per-client adaptive k — delegates to the module-level
        :func:`cohort_budgets` (the same host-side scalar math as the
        sequential reference, so k and bytes can never drift)."""
        return cohort_budgets(
            states, self.cfg, n_samples, adaptive_k, n_cohort, send_h,
            value_bits=self.value_bits, k_min=self.k_min,
            quantize_wire=self.quantize_wire,
        )

    def _upload_manifests(self, cohort, states, ks, n_samples: int, send_h: bool):
        """(active indices, payload manifests, lora rank) for the k > 0
        transmitters — dropped stragglers contribute nothing."""
        active = [i for i, k in enumerate(ks) if k > 0]
        payloads: list[UplinkPayload] = []
        rank = None
        for i in active:
            payload, rank = make_upload_payload(
                self.cfg, cohort[i].client_id, n_samples, ks[i],
                send_h=send_h, value_bits=self.value_bits,
                snr_db=states[i].snr_db, quantize=self.quantize_wire,
            )
            payloads.append(payload)
        return active, payloads, rank

    def _stacked_batches(self, cohort, *, step_major: bool):
        """Each client's next ``local_steps`` private batches, drawn through
        its OWN rng stream (identical to the sequential path).  Returns a
        list of step-major dicts (one per step) or one client-major dict
        with a (C, S, ...) leading layout."""
        per_client = [c.next_train_batches(self.local_steps) for c in cohort]
        keys = per_client[0][0].keys()
        if step_major:
            return [
                {key: jnp.asarray(np.stack([b[s][key] for b in per_client]))
                 for key in keys}
                for s in range(self.local_steps)
            ]
        return {
            key: jnp.asarray(
                np.stack([np.stack([b[s][key] for s in range(self.local_steps)])
                          for b in per_client])
            )
            for key in keys
        }

    def run_round(
        self,
        sel: Sequence[int],
        pub_tokens: jax.Array,
        bcast: BroadcastState | None,
        states: BatchedChannelState | Sequence[ChannelState],
        *,
        adaptive_k: bool,
        send_h: bool,
    ) -> ClientPhase:
        sel = check_unique_cohort(sel)
        cohort = [self.clients[i] for i in sel]
        states = list(states)
        idx, lora, frozen, opt = self._gather_cohort(sel)

        # -- lines 5-7: cohort distillation against the shared broadcast --
        if bcast is not None:
            for _ in range(self.distill_steps):
                lora, opt, _ = self._distill(
                    lora, frozen, opt, bcast.tokens, bcast.logits, bcast.h
                )

        # -- line 8: local fine-tuning, one vmapped update per step --
        for jb in self._stacked_batches(cohort, step_major=True):
            lora, opt, _ = self._train(lora, frozen, opt, jb)

        # -- lines 9-11: public inference + per-client adaptive top-k --
        n_samples = int(pub_tokens.shape[0])
        ks = self._budgets(states, n_samples, adaptive_k, len(cohort), send_h)

        logits, h = self._public(lora, frozen, pub_tokens)  # (C, P, V), (C, P, r)|None

        active, payloads, rank = self._upload_manifests(
            cohort, states, ks, n_samples, send_h
        )
        dense = h_out = None
        if active:
            take = jnp.asarray(active) if len(active) < len(cohort) else None
            act_logits = logits if take is None else logits[take]
            dense = topk_mask_batch(act_logits, [ks[i] for i in active])
            if self.quantize_wire:
                dense = fake_quant_dense(dense)
            if rank is not None and h is not None:
                h_out = h if take is None else h[take]

        self._scatter_cohort(idx, lora, opt)
        return ClientPhase(dense=dense, h=h_out, payloads=payloads, ks=ks)
