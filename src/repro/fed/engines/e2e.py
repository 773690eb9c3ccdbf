"""The whole-round (client + server phase) single-executable engine."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.channel import BatchedChannelState, ChannelState
from repro.core.topk import QuantizedWire, SparseWire
from repro.fed import steps as fed_steps
from repro.fed.client import Client
from repro.fed.engines.base import (
    BroadcastState,
    ClientPhase,
    RoundsTrajectory,
    _channel_scan_ops,
    _ServerOwnerMixin,
    check_unique_cohort,
    k_cap_bucket,
)
from repro.fed.engines.fused import FusedEngine
from repro.fed.store import FleetStore

__all__ = ["FusedE2EEngine"]


class FusedE2EEngine(_ServerOwnerMixin, FusedEngine):
    """Whole-round single-executable engine: client phase AND server phase
    (adaptive aggregation, server distillation, broadcast recomputation) as
    ONE donated, compiled call per round — and the uplink crosses the
    engine/server boundary as the sparse wire format ``(values, indices,
    transmit mask)`` of width ``k_cap`` instead of a densified ``(C, P, V)``
    stack, so the aggregation working set is O(C·P·k_cap).

    The engine owns the server LLM's state for the duration of the run
    (pulled from the :class:`repro.fed.server.Server` at construction);
    :meth:`sync_server` writes the merged parameters back for evaluation,
    and :meth:`broadcast_state` exposes the in-program-computed broadcast to
    the round loop.  Cold-server round 0 and all-dropped rounds are DATA
    (masks) inside the executable, not Python control flow, so one
    executable serves every round of a run (per power-of-two ``k_cap``
    bucket — see :func:`k_cap_bucket`).

    ``shard_clients=True`` places the client phase's cohort axis over the
    process's devices INSIDE the compiled round body (``shard_map`` in
    :func:`repro.fed.steps.make_fused_e2e_round_fn`); the server phase stays
    replicated.  Cohorts that do not divide the device count are padded with
    masked ``k = 0`` duplicate rows exactly like the fused client-phase
    engine — the pad transmits nothing, is excluded from aggregation by its
    all-False wire mask, and its advanced state is discarded before the
    scatter-back.

    :meth:`run_rounds` additionally scans R whole rounds inside one
    compiled call (steady-state dispatch fully amortised) and taps each
    round's server/client accuracy, server-distill loss and mean adaptive
    ``k`` as scanned outputs — a full :class:`RoundsTrajectory` instead of a
    blind block.  The scan carries the WHOLE fleet stack as a donated
    device operand, so it requires the device fleet store; a host store
    (O(cohort) device residency) runs the per-round driver instead.
    """

    name = "fused_e2e"
    handles_server = True

    def __init__(
        self,
        clients: list[Client],
        cfg: ModelConfig,
        *,
        server,
        num_classes: int,
        lr: float = 1e-3,
        distill_lr: float = 1e-3,
        temperature: float = 2.0,
        lam: float = 0.03,
        local_steps: int = 4,
        distill_steps: int = 2,
        server_distill_steps: int = 12,
        aggregation: str = "adaptive",
        restrict_to_support: bool = False,
        value_bits: int = 16,
        k_min: int = 1,
        shard_clients: bool = False,
        use_kernels: bool = False,
        quantize_wire: bool = False,
        compute_dtype: str = "float32",
        fleet_store: "str | FleetStore" = "device",
    ):
        super().__init__(
            clients, cfg, num_classes=num_classes, lr=lr, distill_lr=distill_lr,
            temperature=temperature, lam=lam, local_steps=local_steps,
            distill_steps=distill_steps, restrict_to_support=restrict_to_support,
            value_bits=value_bits, k_min=k_min, use_kernels=use_kernels,
            quantize_wire=quantize_wire, compute_dtype=compute_dtype,
            fleet_store=fleet_store,
        )
        self.shard_clients = shard_clients
        self._fn_kwargs = dict(
            lr=lr, distill_lr=distill_lr, temperature=temperature, lam=lam,
            restrict_to_support=restrict_to_support, local_steps=local_steps,
            distill_steps=distill_steps,
            server_distill_steps=server_distill_steps,
            aggregation=aggregation, shared_backbone=self._shared,
            use_kernels=use_kernels, shard_clients=shard_clients, quantize=quantize_wire,
            compute_dtype=compute_dtype,
        )
        self._num_classes = num_classes
        self._init_server_state(server)
        self._steps: dict = {}
        self._drivers: dict = {}

    # -- compiled-step caches -------------------------------------------
    def _e2e_fn(self, k_cap: int, send_h: bool):
        """The unjitted whole-round body for one (k_cap, send_h) bucket."""
        return fed_steps.make_fused_e2e_round_fn(
            self.cfg, self.server.cfg, self._num_classes,
            k_cap=k_cap, send_h=send_h, **self._fn_kwargs,
        )

    def _e2e_step(self, k_cap: int, send_h: bool):
        key = (k_cap, send_h)
        if key not in self._steps:
            self._steps[key] = jax.jit(
                self._e2e_fn(k_cap, send_h), donate_argnums=(0, 2, 3, 5)
            )
        return self._steps[key]

    # -- single whole round: ONE compiled call ---------------------------
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
        batches = self._stacked_batches(cohort, step_major=False)
        pad, sel_call, batches = self._pad_cohort(sel, batches)
        idx, lora, frozen, opt = self._gather_cohort(sel_call)
        n_samples = int(pub_tokens.shape[0])
        ks = self._budgets(states, n_samples, adaptive_k, len(cohort), send_h)
        k_cap = k_cap_bucket(ks, self.cfg.vocab_size)

        if bcast is not None:
            g_tokens, g_logits, g_h = bcast.tokens, bcast.logits, bcast.h
            g_valid = True
        else:
            g_tokens, g_logits, g_h = self._cold_broadcast(pub_tokens, n_samples)
            g_valid = False

        step = self._e2e_step(k_cap, send_h)
        (lora, opt, self._s_lora, self._s_opt,
         values, indices, scale, b_logits, b_h, self._d_loss) = step(
            lora, frozen, opt, self._s_lora, self._s_frozen, self._s_opt,
            g_tokens, g_logits, g_h, jnp.asarray(g_valid),
            batches, pub_tokens, jnp.asarray(ks + [0] * pad, jnp.int32),
        )
        if pad:  # drop the padded rows before anything observes them
            lora, opt, values, indices, scale, idx = self._drop_pad(
                len(cohort), lora, opt, values, indices, scale, idx
            )
        self._b_tokens, self._b_logits, self._b_h = pub_tokens, b_logits, b_h

        active, payloads, _rank = self._upload_manifests(
            cohort, states, ks, n_samples, send_h
        )
        sparse = None
        if active:
            take = jnp.asarray(active)
            ks_active = jnp.asarray([ks[i] for i in active], jnp.int32)
            mask = (
                jnp.arange(k_cap, dtype=jnp.int32)[None, None, :]
                < ks_active[:, None, None]
            )
            mask = jnp.broadcast_to(mask, values[take].shape)
            if self.quantize_wire:
                sparse = QuantizedWire(
                    values=values[take], scale=scale[take],
                    indices=indices[take], mask=mask,
                    vocab=self.cfg.vocab_size,
                )
            else:
                sparse = SparseWire(
                    values=values[take], indices=indices[take], mask=mask,
                    vocab=self.cfg.vocab_size,
                )

        self._scatter_cohort(idx, lora, opt)
        return ClientPhase(dense=None, h=None, payloads=payloads, ks=ks, sparse=sparse)

    # -- multi-round scan driver ------------------------------------------
    def _rounds_driver(
        self, k_cap: int, send_h: bool, num_rounds: int, n_real: int,
        has_eval: bool, has_chan: bool,
    ):
        key = (k_cap, send_h, num_rounds, n_real, has_eval, has_chan)
        if key in self._drivers:
            return self._drivers[key]
        fn = self._e2e_fn(k_cap, send_h)
        has_h = self.server.cfg.lora is not None
        # in-scan channel replica: scenario dynamics as f32 data, so the
        # same executable serves every preset (rho=0 == i.i.d.)
        chan_step = fed_steps.make_channel_step_fn() if has_chan else None
        # in-scan eval tap: same last-position class-logit accuracy as the
        # host-side make_eval_fn, traced into the scanned round program
        server_eval = fed_steps.make_scan_eval_fn(self.server.cfg, self._num_classes)
        client_eval = fed_steps.make_scan_eval_fn(self.cfg, self._num_classes)

        shared = self._shared

        def driver(fleet_lora, fleet_opt, s_lora, s_opt, frozen, s_frozen,
                   g_tokens, g_logits, g_h, g_valid, sels, kss, pubs, batches,
                   chan, *eval_args):
            if has_chan:
                ch_z0, ch_bad0, ch_w, ch_u, ch_base, rho, p_gb, p_bg, fade = chan

            def body(carry, xs):
                (fleet_lora, fleet_opt, s_lora, s_opt,
                 g_tokens, g_logits, g_h, g_valid, ch_state) = carry
                sel, ks, pub, bat, ch_xs = xs
                lora = jax.tree.map(lambda x: x[sel], fleet_lora)
                opt = jax.tree.map(lambda x: x[sel], fleet_opt)
                # one shared W' broadcasts into the cohort; per-client
                # backbones are fleet-stacked and gather their cohort rows
                # exactly like the LoRA/opt state (frozen_ax=0 downstream)
                frz = frozen if shared else jax.tree.map(lambda x: x[sel], frozen)
                lora, opt, s_lora, s_opt, _v, _i, _sc, b_logits, b_h, d_loss = fn(
                    lora, frz, opt, s_lora, s_frozen, s_opt,
                    g_tokens, g_logits, g_h if has_h else None, g_valid,
                    bat, pub, ks,
                )
                # drop the shard-padding rows (duplicates of sel[0]) BEFORE
                # the scatter-back: .at[sel].set with duplicate indices has
                # unspecified ordering, and the pad's advanced state must
                # never be observed anyway
                lora, opt = self._drop_pad(n_real, lora, opt)
                sel_real = sel[:n_real]
                fleet_lora = jax.tree.map(
                    lambda full, new: full.at[sel_real].set(new), fleet_lora, lora
                )
                fleet_opt = jax.tree.map(
                    lambda full, new: full.at[sel_real].set(new), fleet_opt, opt
                )
                # -- the eval tap: this round's trajectory entry ----------
                tap = {
                    "distill_loss": d_loss,
                    "mean_k": jnp.mean(ks[:n_real].astype(jnp.float32)),
                }
                if has_eval:
                    ev_tokens, ev_labels = eval_args
                    tap["server_acc"] = server_eval(
                        s_lora, s_frozen, ev_tokens, ev_labels
                    )
                    tap["client_acc"] = client_eval(
                        jax.tree.map(lambda x: x[0], lora),
                        frz if shared else jax.tree.map(lambda x: x[0], frz),
                        ev_tokens, ev_labels,
                    )
                if has_chan:
                    # channel state advances as scan carry; the realised
                    # cohort SNR/outage are tapped as scanned outputs
                    ch_z, ch_bad = ch_state
                    w_t, u_t, base_t = ch_xs
                    ch_z, ch_bad, snr = chan_step(
                        ch_z, ch_bad, w_t, u_t, base_t, rho, p_gb, p_bg, fade
                    )
                    ch_state = (ch_z, ch_bad)
                    tap["snr_db"] = snr[sel[:n_real]]
                    tap["outage"] = ch_bad[sel[:n_real]]
                carry = (
                    fleet_lora, fleet_opt, s_lora, s_opt,
                    pub, b_logits, b_h if has_h else g_h, jnp.ones((), bool),
                    ch_state,
                )
                return carry, tap

            ch_state0 = (ch_z0, ch_bad0) if has_chan else ()
            ch_xs_all = (ch_w, ch_u, ch_base) if has_chan else ()
            carry, taps = jax.lax.scan(
                body,
                (fleet_lora, fleet_opt, s_lora, s_opt,
                 g_tokens, g_logits, g_h, g_valid, ch_state0),
                (sels, kss, pubs, batches, ch_xs_all),
                length=num_rounds,
            )
            return carry, taps

        jitted = jax.jit(driver, donate_argnums=(0, 1, 2, 3))
        self._drivers[key] = jitted
        return jitted

    def run_rounds(
        self,
        sels: Sequence[Sequence[int]],
        pubs: Sequence[jax.Array],
        states_per_round: Sequence,
        *,
        adaptive_k: bool,
        send_h: bool,
        eval_tokens: jax.Array | None = None,
        eval_labels: jax.Array | None = None,
        channel_scan: dict | None = None,
    ) -> "RoundsTrajectory":
        """Run R whole federated rounds as ONE compiled ``lax.scan`` — the
        steady-state amortised driver (dispatch cost O(1) for the block).

        ``channel_scan`` (a :meth:`ChannelSimulator.scan_channel_inputs`
        dict) additionally evolves the scenario channel state — AR(1)
        fading ``z``, Gilbert-Elliott outage — INSIDE the scan as carry,
        with every dynamics parameter an f32 data operand: one executable
        serves all scenario presets (``rho = 0`` replays i.i.d.).  The
        per-round realised cohort SNR/outage come back as scanned outputs
        (``RoundsTrajectory.snr_db``/``outage``); budgets stay host-side
        scalar math, priced from the same (seed, round, cid)-keyed chain.

        Per-round cohort selection/channel budgets stay host-side scalar
        math (ledger parity with the round-at-a-time path); the per-round
        observables — server/client accuracy on the given eval arrays, the
        server-distill loss, the mean adaptive ``k`` — are tapped INSIDE the
        scan as scanned outputs, so the block returns a full
        :class:`RoundsTrajectory` instead of running blind.
        Fleet/server/broadcast state advance in place exactly as R
        ``run_round`` calls would.

        ``eval_tokens``/``eval_labels`` (omit both to skip the accuracy tap)
        are evaluated after each round on the server model and on the
        round's first selected client — the same models the host loop's
        per-round evaluation reads.  The split is truncated to whole
        :data:`repro.fed.steps.EVAL_BATCH` batches exactly like the
        host-side evaluator (so the tap and ``make_eval_fn`` read the same
        samples); a split smaller than one batch is rejected.
        """
        if self.store_kind != "device":
            raise RuntimeError(
                "run_rounds scans the WHOLE fleet stack as a donated device "
                "carry, which only fleet_store='device' provides; a host "
                f"store (store_kind={self.store_kind!r}) keeps O(cohort) "
                "device residency — drive rounds one at a time with "
                "run_round instead (rounds.py falls back automatically)"
            )
        sels = [check_unique_cohort(sel) for sel in sels]
        if (eval_tokens is None) != (eval_labels is None):
            raise ValueError("pass eval_tokens and eval_labels together")
        has_eval = eval_tokens is not None
        has_chan = channel_scan is not None
        num_rounds = len(sels)
        if num_rounds == 0:  # degenerate no-op, like zero host-loop rounds
            return RoundsTrajectory(
                ks=[], payloads=[], mean_k=[], distill_loss=[],
                server_acc=[] if has_eval else None,
                client_acc=[] if has_eval else None,
                snr_db=[] if has_chan else None,
                outage=[] if has_chan else None,
            )
        n_samples = int(pubs[0].shape[0])
        n_real = len(sels[0])
        if any(len(sel) != n_real for sel in sels):
            raise ValueError("run_rounds requires equal-size cohorts")

        pad = 0
        all_ks, all_payloads, batch_list, sels_call = [], [], [], []
        for sel, states in zip(sels, states_per_round):
            cohort = [self.clients[i] for i in sel]
            states = list(states)
            ks = self._budgets(states, n_samples, adaptive_k, len(cohort), send_h)
            _active, payloads, _rank = self._upload_manifests(
                cohort, states, ks, n_samples, send_h
            )
            all_ks.append(ks)
            all_payloads.append(payloads)
            batch = self._stacked_batches(cohort, step_major=False)
            pad, sel_call, batch = self._pad_cohort(sel, batch)
            batch_list.append(batch)
            sels_call.append(sel_call)
        k_cap = k_cap_bucket([k for ks in all_ks for k in ks], self.cfg.vocab_size)

        sels_arr = jnp.asarray(np.asarray(sels_call), jnp.int32)  # (R, C+pad)
        kss_arr = jnp.asarray(  # (R, C+pad); pad rows transmit nothing
            np.asarray([ks + [0] * pad for ks in all_ks]), jnp.int32
        )
        pubs_arr = jnp.stack([jnp.asarray(p) for p in pubs])  # (R, P, L)
        batches = jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list)

        if self._b_logits is not None:
            g_tokens, g_logits, g_h = self._b_tokens, self._b_logits, self._b_h
            g_valid = True
        else:
            g_tokens, g_logits, g_h = self._cold_broadcast(pubs_arr[0], n_samples)
            g_valid = False

        eval_args = ()
        if has_eval:
            # whole EVAL_BATCH batches only — the host evaluator's walk, and
            # the precondition of make_scan_eval_fn's bounded-memory chunking
            seen = (
                int(eval_tokens.shape[0]) // fed_steps.EVAL_BATCH
            ) * fed_steps.EVAL_BATCH
            if seen == 0:
                raise ValueError(
                    f"eval split of {int(eval_tokens.shape[0])} samples is "
                    f"smaller than one eval batch ({fed_steps.EVAL_BATCH})"
                )
            eval_args = (
                jnp.asarray(eval_tokens[:seen]), jnp.asarray(eval_labels[:seen])
            )
        chan_ops = _channel_scan_ops(channel_scan, num_rounds) if has_chan else ()
        driver = self._rounds_driver(
            k_cap, send_h, num_rounds, n_real, has_eval, has_chan
        )
        carry, taps = driver(
            self._lora, self._opt, self._s_lora, self._s_opt,
            self._frozen, self._s_frozen,
            g_tokens, g_logits, g_h, jnp.asarray(g_valid),
            sels_arr, kss_arr, pubs_arr, batches, chan_ops, *eval_args,
        )
        (self._lora, self._opt, self._s_lora, self._s_opt,
         self._b_tokens, self._b_logits, self._b_h, _valid, _chan) = carry
        self._d_loss = taps["distill_loss"][-1]

        def _tolist(name):
            return [float(x) for x in np.asarray(taps[name])]

        snr_db = outage = None
        if has_chan:
            snr_db = [[float(x) for x in row] for row in np.asarray(taps["snr_db"])]
            outage = [[bool(x) for x in row] for row in np.asarray(taps["outage"])]
        return RoundsTrajectory(
            ks=all_ks,
            payloads=all_payloads,
            mean_k=_tolist("mean_k"),
            distill_loss=_tolist("distill_loss"),
            server_acc=_tolist("server_acc") if has_eval else None,
            client_acc=_tolist("client_acc") if has_eval else None,
            snr_db=snr_db,
            outage=outage,
        )
