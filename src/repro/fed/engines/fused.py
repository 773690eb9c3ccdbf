"""The fused single-jit client-phase engine."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.channel import BatchedChannelState, ChannelState
from repro.fed import steps as fed_steps
from repro.fed.client import Client
from repro.fed.engines.base import (
    BroadcastState,
    ClientPhase,
    check_unique_cohort,
    fake_quant_dense,
)
from repro.fed.engines.batched import BatchedEngine
from repro.fed.store import FleetStore

__all__ = ["FusedEngine"]


class FusedEngine(BatchedEngine):
    """Single-jit round-body executor: the batched engine's per-phase calls
    (distill steps, fine-tune steps, public inference, top-k) collapse into
    ONE donated, compiled step per round (`fed_steps.make_fused_round_fn`).

    Per-client adaptive ``k`` enters the program as DATA (int32 per client),
    so one executable serves every round regardless of the channel
    realisation; the uplink sparsifier is the threshold-semantics bisection
    (ties at the k-th value are kept) — pure-jnp ``topk_mask_dynamic`` by
    default, or the per-row-budget Pallas kernel with ``use_kernels=True``.
    Byte accounting still uses the exact host-side ``k``s, so the ledger is
    identical to the other engines.

    ``shard_clients=True`` additionally places the leading client axis over
    the process's devices with ``shard_map``; a cohort that does not divide
    the device count is padded with masked duplicate rows (``k = 0`` — they
    transmit nothing, are excluded from aggregation, and their advanced
    state is discarded before the scatter-back).  On CPU this is testable
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """

    name = "fused"

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
        last_only: bool = True,
        shard_clients: bool = False,
        use_kernels: bool = False,
        class_head_only: bool = True,
        quantize_wire: bool = False,
        compute_dtype: str = "float32",
        fleet_store: "str | FleetStore" = "device",
    ):
        super().__init__(
            clients, cfg, num_classes=num_classes, lr=lr, distill_lr=distill_lr,
            temperature=temperature, lam=lam, local_steps=local_steps,
            distill_steps=distill_steps, restrict_to_support=restrict_to_support,
            value_bits=value_bits, k_min=k_min, last_only=last_only,
            class_head_only=class_head_only, quantize_wire=quantize_wire,
            fleet_store=fleet_store,
        )
        self.shard_clients = shard_clients
        self.compute_dtype = compute_dtype

        def fused(n_distill: int):
            fn = fed_steps.make_fused_round_fn(
                cfg, num_classes, lr=lr, distill_lr=distill_lr,
                temperature=temperature, lam=lam,
                restrict_to_support=restrict_to_support,
                local_steps=local_steps, distill_steps=n_distill,
                shared_backbone=self._shared, last_only=last_only,
                use_kernels=use_kernels, class_head_only=class_head_only,
                compute_dtype=compute_dtype,
            )
            if shard_clients:
                fn = self._shard_over_clients(fn)
            return jax.jit(fn, donate_argnums=(0, 2))

        self._fused_warm = fused(distill_steps)
        self._fused_cold = fused(0)  # round 0: no broadcast knowledge yet

    def _shard_over_clients(self, fn):
        from jax.sharding import PartitionSpec as P

        from repro.sharding import COHORT_AXIS, cohort_mesh

        c, r = P(COHORT_AXIS), P()
        frozen_spec = r if self._shared else c
        return jax.shard_map(
            fn,
            mesh=cohort_mesh(),
            in_specs=(c, frozen_spec, c, r, r, r, c, r, c),
            out_specs=(c, c, c, c),
            check_vma=False,
        )

    def _pad_cohort(self, sel: Sequence[int], batches: dict):
        """THE masked k = 0 shard-padding contract, in one place (used by the
        fused client-phase round, the e2e whole round, and the e2e
        multi-round scan): a cohort that does not divide the device count is
        extended with duplicate rows of client ``sel[0]`` that ride at
        ``k = 0`` — they compute alongside the cohort but transmit nothing,
        and every caller discards their advanced state before it can be
        observed.  Their batches are COPIES (``sel[0]``'s rng stream
        advances exactly once).  Returns ``(pad, sel + pad dups, padded
        batches)``; a no-op (pad 0) unless ``shard_clients``."""
        pad = (-len(sel)) % jax.device_count() if self.shard_clients else 0
        if not pad:
            return 0, list(sel), batches
        batches = {
            key: jnp.concatenate([v, jnp.repeat(v[:1], pad, axis=0)])
            for key, v in batches.items()
        }
        return pad, list(sel) + [sel[0]] * pad, batches

    def prefetch_cohort(self, sel: Sequence[int]) -> None:
        """Prefetch hint, shard-padding aware: the store must stage exactly
        the rows :meth:`run_round` will fetch (``sel`` + its pad
        duplicates), or the hint misses."""
        sel = list(sel)
        if self.shard_clients and sel:
            pad = (-len(sel)) % jax.device_count()
            sel = sel + [sel[0]] * pad
        self._store.prefetch(sel)

    @staticmethod
    def _drop_pad(n: int, *trees):
        """Inverse of :meth:`_pad_cohort`: truncate every given pytree (or
        array, or None) back to the ``n`` real leading-cohort rows — the one
        place the 'pad state must never be observed' side of the contract
        lives."""
        out = tuple(jax.tree.map(lambda x: x[:n], t) for t in trees)
        return out if len(out) > 1 else out[0]

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
        batches = self._stacked_batches(cohort, step_major=False)  # (C, S, ...)
        pad, sel_call, batches = self._pad_cohort(sel, batches)
        idx, lora, frozen, opt = self._gather_cohort(sel_call)
        n_samples = int(pub_tokens.shape[0])
        ks = self._budgets(states, n_samples, adaptive_k, len(cohort), send_h)

        # -- the whole client phase: ONE compiled, donated call --
        if bcast is not None:
            step = self._fused_warm
            g_tokens, g_logits, g_h = bcast.tokens, bcast.logits, bcast.h
        else:
            step = self._fused_cold  # g_* operands are unused and DCE'd
            g_tokens, g_logits, g_h = pub_tokens, jnp.zeros(
                (n_samples, self.cfg.vocab_size), jnp.float32), None
        lora, opt, dense_all, h_all = step(
            lora, frozen, opt, g_tokens, g_logits, g_h, batches, pub_tokens,
            jnp.asarray(ks + [0] * pad, jnp.int32),
        )
        if pad:  # drop the padded rows before anything observes them
            lora, opt, dense_all, h_all, idx = self._drop_pad(
                len(cohort), lora, opt, dense_all, h_all, idx
            )

        active, payloads, rank = self._upload_manifests(
            cohort, states, ks, n_samples, send_h
        )
        dense = h_out = None
        if active:
            take = jnp.asarray(active) if len(active) < len(cohort) else None
            dense = dense_all if take is None else dense_all[take]
            if self.quantize_wire:
                dense = fake_quant_dense(dense)
            if rank is not None and h_all is not None:
                h_out = h_all if take is None else h_all[take]

        self._scatter_cohort(idx, lora, opt)
        return ClientPhase(dense=dense, h=h_out, payloads=payloads, ks=ks)
