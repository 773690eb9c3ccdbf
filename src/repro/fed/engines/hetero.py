"""Family-bucketed engines for heterogeneous fleets."""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import BatchedChannelState, ChannelState
from repro.core.protocol import UplinkPayload
from repro.core.topk import (
    QuantizedWire,
    SparseWire,
    concat_wires,
    take_wire_rows,
)
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
from repro.fed.engines.batched import BatchedEngine
from repro.fed.engines.fused import FusedEngine
from repro.fed.store import FleetStore

__all__ = ["HeteroClientEngine", "HeteroFusedE2EEngine"]


class HeteroClientEngine:
    """Family-bucketed CLIENT-phase engine for heterogeneous fleets.

    The fleet is partitioned into homogeneous family buckets
    (:func:`repro.fed.cohort.partition_fleet`); each bucket runs its own
    batched/fused sub-engine — one vmapped, donated executable per family —
    and a round's uploads merge in the model-agnostic logit space: the
    per-bucket densified stacks concatenate into one cohort-ordered
    ``(T, P, V)`` stack (vocab is the shared exchange contract, so the
    unchanged server aggregation consumes it exactly as a homogeneous
    cohort's).  ``ks``/payload accounting is reassembled in cohort order,
    so the ledger is bit-identical to the sequential reference over the
    same clients.

    Fleet-state ownership (including ``fleet_store="host"``) lives in the
    per-bucket sub-engines: each bucket carries its own
    :class:`repro.fed.store.FleetStore`, so a heterogeneous fleet streams
    cohorts bucket-by-bucket with O(cohort) device residency.
    """

    name = "hetero"

    def __init__(self, kind: str, clients: list[Client], **kwargs):
        from repro.fed.cohort import fleet_index, partition_fleet, validate_family_contracts

        self.buckets = partition_fleet(clients)
        validate_family_contracts(self.buckets)
        self.kind = kind
        sub_cls = {"batched": BatchedEngine, "fused": FusedEngine}[kind]
        sub_kwargs = dict(kwargs)
        if kind == "batched":
            sub_kwargs.pop("shard_clients", None)
            sub_kwargs.pop("use_kernels", None)
        self._engines = [
            sub_cls([clients[i] for i in b.client_ids], b.cfg, **sub_kwargs)
            for b in self.buckets
        ]
        self._where = fleet_index(self.buckets)

    @property
    def store_kind(self) -> str:
        return self._engines[0].store_kind

    def client_params(self, cid: int):
        bi, local = self._where[int(cid)]
        return self._engines[bi].client_params(local)

    def fleet_state(self) -> dict:
        return {f"bucket{i}": e.fleet_state() for i, e in enumerate(self._engines)}

    def load_fleet_state(self, state: dict) -> None:
        for i, e in enumerate(self._engines):
            e.load_fleet_state(state[f"bucket{i}"])

    def save_fleet_shards(self, dir_path: str) -> None:
        """Shard every bucket's fleet into ONE directory (per-bucket
        ``bucket{i}_*`` prefixes keep the ranges disjoint)."""
        for i, e in enumerate(self._engines):
            e.save_fleet_shards(dir_path, prefix=f"bucket{i}")

    def load_fleet_shards(self, dir_path: str) -> None:
        for i, e in enumerate(self._engines):
            e.load_fleet_shards(dir_path, prefix=f"bucket{i}")

    def prefetch_cohort(self, sel: Sequence[int]) -> None:
        """Forward the next-round hint bucket-locally, exactly as
        :meth:`run_round` will fetch it."""
        from repro.fed.cohort import split_cohort

        for b, _pos, local in split_cohort(self.buckets, sel):
            self._engines[b.index].prefetch_cohort(local)

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
        from repro.fed.cohort import split_cohort

        sel = check_unique_cohort(sel)
        states = list(states)
        ks = [0] * len(sel)
        merged = []  # (cohort position, dense row, h row, payload)
        for b, pos, local in split_cohort(self.buckets, sel):
            phase = self._engines[b.index].run_round(
                local, pub_tokens, bcast, [states[p] for p in pos],
                adaptive_k=adaptive_k, send_h=send_h,
            )
            for p, k in zip(pos, phase.ks):
                ks[p] = k
            tx = [p for p, k in zip(pos, phase.ks) if k > 0]
            for j, p in enumerate(tx):
                merged.append((
                    p,
                    None if phase.dense is None else phase.dense[j],
                    None if phase.h is None else phase.h[j],
                    phase.payloads[j],
                ))
        # transmitters back into cohort order: the union stack then reads
        # exactly like a homogeneous engine's (and the payload manifest
        # order matches the sequential reference)
        merged.sort(key=lambda entry: entry[0])
        dense = jnp.stack([d for _, d, _, _ in merged]) if merged else None
        h = (
            jnp.stack([h_row for _, _, h_row, _ in merged])
            if merged and merged[0][2] is not None
            else None
        )
        return ClientPhase(
            dense=dense, h=h, payloads=[m[3] for m in merged], ks=ks
        )


class HeteroFusedE2EEngine(_ServerOwnerMixin):
    """Family-bucketed end-to-end engine: one fused client-phase executable
    PER FAMILY BUCKET, one union sparse wire, one compiled server phase.

    This is the paper's actual scenario — clients with different
    architectures federating through the shared logit space — served by the
    fast-engine machinery:

    * the fleet partitions into homogeneous family buckets
      (`repro.fed.cohort`); each bucket keeps its LoRA/opt state in its own
      :class:`repro.fed.store.FleetStore` (a :class:`BatchedEngine` per
      bucket is the state holder) and runs its whole client phase —
      distill, fine-tune scan, public inference, sparse-wire top-k with
      per-client ``k`` as DATA — as one donated compiled call
      (:func:`repro.fed.steps.make_bucket_client_phase_fn`), with
      ``frozen_ax=0`` stacked backbones for buckets whose clients carry
      distinct frozen trees;
    * the buckets' wires concatenate into ONE vocab-indexed union wire
      (:func:`repro.core.topk.concat_wires` semantics, materialised
      in-order here), and the eq.-8 projections align across families by
      the shared LoRA rank — so the UNCHANGED server phase
      (:func:`repro.fed.steps.make_server_phase_fn`: wire aggregation,
      server-distill scan, broadcast recompute) runs exactly once per
      round, family-blind;
    * :meth:`run_rounds` scans R whole heterogeneous rounds inside one
      compiled dispatch: per-bucket fleet state rides in the scan carry
      (frozen stacks included — device store only; a host store falls back
      to the per-round driver), per-round variable family participation is
      handled by padding each bucket to its block-wide max cohort slice
      with masked ``k = 0`` rows that compute alongside the round but
      transmit nothing and scatter into a write-only scratch row, and the
      in-scan eval tap reports the server accuracy plus ONE accuracy PER
      FAMILY.
    """

    name = "hetero_fused_e2e"

    def __init__(
        self,
        clients: list[Client],
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
        from repro.fed.cohort import fleet_index, partition_fleet, validate_family_contracts

        if shard_clients:
            raise NotImplementedError(
                "shard_clients is not supported for heterogeneous fleets yet:"
                " each family bucket would need its own divisible client-axis"
                " placement"
            )
        self.buckets = partition_fleet(clients)
        validate_family_contracts(self.buckets, server_cfg=server.cfg)
        self._where = fleet_index(self.buckets)
        self.clients = clients
        self.vocab = self.buckets[0].cfg.vocab_size
        self._num_classes = num_classes
        self._local_steps = local_steps
        self.quantize_wire = quantize_wire
        sub_kwargs = dict(
            num_classes=num_classes, lr=lr, distill_lr=distill_lr,
            temperature=temperature, lam=lam, local_steps=local_steps,
            distill_steps=distill_steps,
            restrict_to_support=restrict_to_support, value_bits=value_bits,
            k_min=k_min, quantize_wire=quantize_wire, fleet_store=fleet_store,
        )
        # one BatchedEngine per bucket as the stacked-fleet STATE HOLDER
        # (gather/scatter/budget/batch plumbing); its per-phase steps are
        # never invoked — the bucket client-phase executable below runs the
        # round
        self._b = [
            BatchedEngine([clients[i] for i in b.client_ids], b.cfg, **sub_kwargs)
            for b in self.buckets
        ]
        self._phase_kwargs = dict(
            lr=lr, distill_lr=distill_lr, temperature=temperature, lam=lam,
            restrict_to_support=restrict_to_support, local_steps=local_steps,
            distill_steps=distill_steps, quantize=quantize_wire,
            compute_dtype=compute_dtype,
        )
        self._server_kwargs = dict(
            vocab=self.vocab, distill_lr=distill_lr, temperature=temperature,
            lam=lam, restrict_to_support=restrict_to_support,
            server_distill_steps=server_distill_steps,
            aggregation=aggregation, use_kernels=use_kernels, quantize=quantize_wire,
            compute_dtype=compute_dtype,
        )
        self._init_server_state(server)
        self._client_steps: dict = {}
        self._server_steps: dict = {}
        self._drivers: dict = {}

    # -- compiled-step caches -------------------------------------------
    def _client_phase_fn(self, bi: int, k_cap: int):
        """One bucket's unjitted client-phase body (for the scan driver)."""
        b = self.buckets[bi]
        return fed_steps.make_bucket_client_phase_fn(
            b.cfg, self._num_classes, k_cap=k_cap,
            shared_backbone=self._b[bi]._shared, **self._phase_kwargs,
        )

    def _client_step(self, bi: int, k_cap: int):
        key = (bi, k_cap)
        if key not in self._client_steps:
            self._client_steps[key] = jax.jit(
                self._client_phase_fn(bi, k_cap), donate_argnums=(0, 2)
            )
        return self._client_steps[key]

    def _server_step(self, send_h: bool):
        if send_h not in self._server_steps:
            self._server_steps[send_h] = jax.jit(
                fed_steps.make_server_phase_fn(
                    self.server.cfg, send_h=send_h, **self._server_kwargs
                ),
                donate_argnums=(0, 2),
            )
        return self._server_steps[send_h]

    @property
    def store_kind(self) -> str:
        return self._b[0].store_kind

    def client_params(self, cid: int):
        bi, local = self._where[int(cid)]
        return self._b[bi].client_params(local)

    def fleet_state(self) -> dict:
        return {f"bucket{i}": b.fleet_state() for i, b in enumerate(self._b)}

    def load_fleet_state(self, state: dict) -> None:
        for i, b in enumerate(self._b):
            b.load_fleet_state(state[f"bucket{i}"])

    def save_fleet_shards(self, dir_path: str) -> None:
        for i, b in enumerate(self._b):
            b.save_fleet_shards(dir_path, prefix=f"bucket{i}")

    def load_fleet_shards(self, dir_path: str) -> None:
        for i, b in enumerate(self._b):
            b.load_fleet_shards(dir_path, prefix=f"bucket{i}")

    def prefetch_cohort(self, sel: Sequence[int]) -> None:
        from repro.fed.cohort import split_cohort

        for b, _pos, local in split_cohort(self.buckets, sel):
            self._b[b.index].prefetch_cohort(local)

    # -- one whole heterogeneous round -----------------------------------
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
        from repro.fed.cohort import split_cohort

        sel = check_unique_cohort(sel)
        states = list(states)
        n_samples = int(pub_tokens.shape[0])
        parts = split_cohort(self.buckets, sel)

        # budgets first (host scalar math, cohort order — ledger parity)
        ks = [0] * len(sel)
        budgets = []
        for b, pos, local in parts:
            ks_b = self._b[b.index]._budgets(
                [states[p] for p in pos], n_samples, adaptive_k, len(pos), send_h
            )
            budgets.append(ks_b)
            for p, k in zip(pos, ks_b):
                ks[p] = k
        k_cap = k_cap_bucket(ks, self.vocab)

        if bcast is not None:
            g_tokens, g_logits, g_h = bcast.tokens, bcast.logits, bcast.h
            g_valid = True
        else:
            g_tokens, g_logits, g_h = self._cold_broadcast(pub_tokens, n_samples)
            g_valid = False
        g_valid_arr = jnp.asarray(g_valid)

        # -- client phase: one donated compiled call per family bucket --
        wires: list[SparseWire | QuantizedWire] = []
        h_parts: list = []
        order: list[int] = []  # cohort position of each bucket-concat row
        payloads_by_pos: dict[int, UplinkPayload] = {}
        for (b, pos, local), ks_b in zip(parts, budgets):
            be = self._b[b.index]
            cohort = [be.clients[j] for j in local]
            batches = be._stacked_batches(cohort, step_major=False)
            idx, lora, frozen, opt = be._gather_cohort(local)
            lora, opt, v, i, m, sc, h = self._client_step(b.index, k_cap)(
                lora, frozen, opt, g_tokens, g_logits, g_h, g_valid_arr,
                batches, pub_tokens, jnp.asarray(ks_b, jnp.int32),
            )
            be._scatter_cohort(idx, lora, opt)
            _active, pl, _rank = be._upload_manifests(
                cohort, [states[p] for p in pos], ks_b, n_samples, send_h
            )
            it = iter(pl)
            for j, p in enumerate(pos):
                if ks_b[j] > 0:
                    payloads_by_pos[p] = next(it)
            if self.quantize_wire:
                wires.append(QuantizedWire(
                    values=v, scale=sc, indices=i, mask=m, vocab=self.vocab
                ))
            else:
                wires.append(SparseWire(values=v, indices=i, mask=m, vocab=self.vocab))
            h_parts.append(h)
            order.extend(pos)

        # -- union wire: the buckets' wires merge in the shared vocab-indexed
        # logit space, rows permuted back into cohort order; then ONE
        # family-blind compiled server phase --
        inv = np.argsort(np.asarray(order))
        union = take_wire_rows(concat_wires(wires), inv)
        h_all = None
        if h_parts[0] is not None:
            h_all = jnp.concatenate(h_parts)[jnp.asarray(inv)]
        union_scale = union.scale if self.quantize_wire else None
        (self._s_lora, self._s_opt, b_logits, b_h, self._d_loss) = (
            self._server_step(send_h)(
                self._s_lora, self._s_frozen, self._s_opt,
                union.values, union.indices, union.mask, union_scale, h_all,
                jnp.asarray(ks, jnp.int32), pub_tokens,
            )
        )
        self._b_tokens, self._b_logits, self._b_h = pub_tokens, b_logits, b_h

        tx = [p for p in range(len(sel)) if ks[p] > 0]
        sparse = take_wire_rows(union, tx) if tx else None
        return ClientPhase(
            dense=None, h=None, payloads=[payloads_by_pos[p] for p in tx],
            ks=ks, sparse=sparse,
        )

    # -- R heterogeneous rounds as ONE compiled lax.scan ------------------
    def _hetero_rounds_driver(
        self, k_cap: int, send_h: bool, num_rounds: int, n_real: int,
        caps: tuple[int, ...], has_eval: bool, has_chan: bool,
    ):
        key = (k_cap, send_h, num_rounds, n_real, caps, has_eval, has_chan)
        if key in self._drivers:
            return self._drivers[key]
        chan_step = fed_steps.make_channel_step_fn() if has_chan else None
        fns = [self._client_phase_fn(bi, k_cap) for bi in range(len(self.buckets))]
        server_fn = fed_steps.make_server_phase_fn(
            self.server.cfg, send_h=send_h, **self._server_kwargs
        )
        has_h = self.server.cfg.lora is not None
        shared = [be._shared for be in self._b]
        sizes = [b.size for b in self.buckets]
        server_eval = fed_steps.make_scan_eval_fn(self.server.cfg, self._num_classes)
        family_evals = [
            fed_steps.make_scan_eval_fn(b.cfg, self._num_classes) for b in self.buckets
        ]

        def driver(fleet_loras, fleet_opts, s_lora, s_opt, frozens, s_frozen,
                   g_tokens, g_logits, g_h, g_valid,
                   gathers, scatters, kss_b, batches_b, kss_all, pubs,
                   chan, *eval_args):
            if has_chan:
                (ch_z0, ch_bad0, ch_w, ch_u, ch_base,
                 rho, p_gb, p_bg, fade, sels_data) = chan

            def body(carry, xs):
                (fleet_loras, fleet_opts, s_lora, s_opt,
                 g_tokens, g_logits, g_h, g_valid, ch_state) = carry
                gath, scat, ksb, bat, ks_all, pub, ch_xs = xs
                vs, idxs, ms, scs, hs = [], [], [], [], []
                new_loras, new_opts = [], []
                for f, fn in enumerate(fns):
                    # gather this round's (padded) bucket slice; pads
                    # duplicate a real row for COMPUTE but scatter into the
                    # write-only scratch row sizes[f], so their advanced
                    # state is never observable
                    lora = jax.tree.map(lambda x: x[gath[f]], fleet_loras[f])
                    opt = jax.tree.map(lambda x: x[gath[f]], fleet_opts[f])
                    frz = (
                        frozens[f] if shared[f]
                        else jax.tree.map(lambda x: x[gath[f]], frozens[f])
                    )
                    lora, opt, v, i, m, sc, h = fn(
                        lora, frz, opt, g_tokens, g_logits,
                        g_h if has_h else None, g_valid, bat[f], pub, ksb[f],
                    )
                    new_loras.append(jax.tree.map(
                        lambda full, new: full.at[scat[f]].set(new),
                        fleet_loras[f], lora,
                    ))
                    new_opts.append(jax.tree.map(
                        lambda full, new: full.at[scat[f]].set(new),
                        fleet_opts[f], opt,
                    ))
                    vs.append(v)
                    idxs.append(i)
                    ms.append(m)
                    scs.append(sc)
                    hs.append(h)
                # the union wire: bucket-concatenated rows, vocab-indexed —
                # aggregation is row-permutation-invariant, so no cohort
                # reordering is needed in-program
                v_all = jnp.concatenate(vs)
                i_all = jnp.concatenate(idxs)
                m_all = jnp.concatenate(ms)
                sc_all = jnp.concatenate(scs) if scs[0] is not None else None
                h_all = jnp.concatenate(hs) if hs[0] is not None else None
                s_lora, s_opt, b_logits, b_h, d_loss = server_fn(
                    s_lora, s_frozen, s_opt, v_all, i_all, m_all, sc_all,
                    h_all, ks_all, pub,
                )
                # pad rows ride at k = 0, so the real cohort's mean is just
                # the padded sum over the true cohort size
                tap = {
                    "distill_loss": d_loss,
                    "mean_k": jnp.sum(ks_all.astype(jnp.float32)) / n_real,
                }
                if has_eval:
                    ev_tokens, ev_labels = eval_args
                    tap["server_acc"] = server_eval(
                        s_lora, s_frozen, ev_tokens, ev_labels
                    )
                    fam = []
                    for f in range(len(fns)):
                        # post-scatter fleet row gath[f][0]: the family's
                        # first selected client this round (or its local
                        # client 0, untouched, when the family sat out)
                        lf = jax.tree.map(
                            lambda x: x[gath[f][0]], new_loras[f]
                        )
                        ff = (
                            frozens[f] if shared[f]
                            else jax.tree.map(lambda x: x[gath[f][0]], frozens[f])
                        )
                        fam.append(family_evals[f](lf, ff, ev_tokens, ev_labels))
                    tap["family_client_acc"] = jnp.stack(fam)
                if has_chan:
                    # hetero cohorts are bucket-local in-program; the global
                    # cohort ids ride along as data purely for the tap gather
                    ch_z, ch_bad = ch_state
                    w_t, u_t, base_t, sel_real = ch_xs
                    ch_z, ch_bad, snr = chan_step(
                        ch_z, ch_bad, w_t, u_t, base_t, rho, p_gb, p_bg, fade
                    )
                    ch_state = (ch_z, ch_bad)
                    tap["snr_db"] = snr[sel_real]
                    tap["outage"] = ch_bad[sel_real]
                carry = (
                    tuple(new_loras), tuple(new_opts), s_lora, s_opt,
                    pub, b_logits, b_h if has_h else g_h, jnp.ones((), bool),
                    ch_state,
                )
                return carry, tap

            ch_state0 = (ch_z0, ch_bad0) if has_chan else ()
            ch_xs_all = (ch_w, ch_u, ch_base, sels_data) if has_chan else ()
            carry, taps = jax.lax.scan(
                body,
                (fleet_loras, fleet_opts, s_lora, s_opt,
                 g_tokens, g_logits, g_h, g_valid, ch_state0),
                (gathers, scatters, kss_b, batches_b, kss_all, pubs,
                 ch_xs_all),
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
    ) -> RoundsTrajectory:
        """Run R whole heterogeneous rounds as ONE compiled ``lax.scan``.

        ``channel_scan`` evolves the scenario channel state inside the scan
        exactly as on the homogeneous path (see
        :meth:`FusedE2EEngine.run_rounds`); the global cohort ids ride
        along as data so the per-round SNR/outage tap can gather the
        fleet-wide realisation into cohort order.

        Family participation varies per round, but every compiled shape is
        static: each bucket is padded to its block-wide maximum cohort slice
        (at least one row) with masked ``k = 0`` rows.  A pad row gathers a
        real client's state so the computation stays well-posed, contributes
        nothing to the union wire (all-False transmit mask), consumes no
        private batch (its batch rows are zeros), and scatters its advanced
        state into a write-only scratch row appended past the bucket's fleet
        — ``.at[sel].set`` duplicate-index hazards land only there.  Per
        round, the eval tap reports server accuracy and one accuracy per
        family bucket; ``client_acc`` is the cohort's first selected
        client's family entry (the host loop's metric).
        """
        from repro.fed.cohort import split_cohort

        if self.store_kind != "device":
            raise RuntimeError(
                "run_rounds scans every bucket's WHOLE fleet stack as a "
                "donated device carry, which only fleet_store='device' "
                f"provides; a host store (store_kind={self.store_kind!r}) "
                "keeps O(cohort) device residency — drive rounds one at a "
                "time with run_round instead (rounds.py falls back "
                "automatically)"
            )
        sels = [check_unique_cohort(sel) for sel in sels]
        if (eval_tokens is None) != (eval_labels is None):
            raise ValueError("pass eval_tokens and eval_labels together")
        has_eval = eval_tokens is not None
        has_chan = channel_scan is not None
        num_rounds = len(sels)
        if num_rounds == 0:
            return RoundsTrajectory(
                ks=[], payloads=[], mean_k=[], distill_loss=[],
                server_acc=[] if has_eval else None,
                client_acc=[] if has_eval else None,
                family_client_acc=[] if has_eval else None,
                snr_db=[] if has_chan else None,
                outage=[] if has_chan else None,
            )
        n_samples = int(pubs[0].shape[0])
        n_real = len(sels[0])
        if any(len(sel) != n_real for sel in sels):
            raise ValueError("run_rounds requires equal-size cohorts")

        F = len(self.buckets)
        # -- host pre-pass: budgets/payloads (ledger), per-bucket slices --
        all_ks, all_payloads = [], []
        per_round: list[list[tuple[list[int], list[int], list[int]]]] = []
        first_bucket: list[int] = []  # family of sel[0], per round
        for sel, states in zip(sels, states_per_round):
            states = list(states)
            parts = {b.index: (pos, local)
                     for b, pos, local in split_cohort(self.buckets, sel)}
            ks = [0] * len(sel)
            round_rows = []
            for f in range(F):
                pos, local = parts.get(f, ([], []))
                ks_b = self._b[f]._budgets(
                    [states[p] for p in pos], n_samples, adaptive_k,
                    len(pos), send_h,
                ) if pos else []
                for p, k in zip(pos, ks_b):
                    ks[p] = k
                round_rows.append((pos, local, ks_b))
            payloads = []
            for f, (pos, local, ks_b) in enumerate(round_rows):
                if not pos:
                    continue
                be = self._b[f]
                _a, pl, _r = be._upload_manifests(
                    [be.clients[j] for j in local],
                    [states[p] for p in pos], ks_b, n_samples, send_h,
                )
                it = iter(pl)
                payloads.extend(
                    (p, next(it)) for p, k in zip(pos, ks_b) if k > 0
                )
            payloads.sort(key=lambda t: t[0])
            all_ks.append(ks)
            all_payloads.append([pl for _, pl in payloads])
            per_round.append(round_rows)
            fb = [f for f, (pos, _l, _k) in enumerate(round_rows) if 0 in pos]
            first_bucket.append(fb[0])
        k_cap = k_cap_bucket(
            [k for ks in all_ks for k in ks], self.vocab
        )
        caps = tuple(
            max(max((len(per_round[r][f][0]) for r in range(num_rounds)),
                    default=0), 1)
            for f in range(F)
        )

        # -- per-bucket padded scan inputs (gather/scatter/ks/batches) --
        gathers, scatters, kss_b, batches_b = [], [], [], []
        for f in range(F):
            be = self._b[f]
            cap = caps[f]
            g_rows, s_rows, k_rows, b_rows = [], [], [], []
            for r in range(num_rounds):
                pos, local, ks_b = per_round[r][f]
                pad = cap - len(local)
                anchor = local[0] if local else 0
                g_rows.append(local + [anchor] * pad)
                s_rows.append(local + [self.buckets[f].size] * pad)
                k_rows.append(ks_b + [0] * pad)
                if local:
                    bat = be._stacked_batches(
                        [be.clients[j] for j in local], step_major=False
                    )
                    bat = {
                        key: np.concatenate(
                            [np.asarray(v)]
                            + [np.zeros_like(np.asarray(v[:1]))] * pad
                        ) if pad else np.asarray(v)
                        for key, v in bat.items()
                    }
                else:
                    # the family sits this round out: all-pad slice, zero
                    # batches (no client rng stream is consumed)
                    shapes = self._zero_batch_shapes(be)
                    bat = {
                        key: np.zeros((cap,) + shape, dtype)
                        for key, (shape, dtype) in shapes.items()
                    }
                b_rows.append(bat)
            gathers.append(jnp.asarray(np.asarray(g_rows), jnp.int32))
            scatters.append(jnp.asarray(np.asarray(s_rows), jnp.int32))
            kss_b.append(jnp.asarray(np.asarray(k_rows), jnp.int32))
            batches_b.append({
                key: jnp.asarray(np.stack([row[key] for row in b_rows]))
                for key in b_rows[0]
            })
        kss_all = jnp.asarray(  # (R, sum caps) in bucket-concat order
            np.concatenate([np.asarray(k) for k in kss_b], axis=1), jnp.int32
        )
        pubs_arr = jnp.stack([jnp.asarray(p) for p in pubs])

        # fleet state + one write-only scratch row per bucket (pad target)
        fleet_loras, fleet_opts, frozens = [], [], []
        for be in self._b:
            fleet_loras.append(jax.tree.map(
                lambda x: jnp.concatenate([x, jnp.zeros_like(x[:1])]), be._lora
            ))
            fleet_opts.append(jax.tree.map(
                lambda x: jnp.concatenate([x, jnp.zeros_like(x[:1])]), be._opt
            ))
            frozens.append(be._frozen)

        if self._b_logits is not None:
            g_tokens, g_logits, g_h = self._b_tokens, self._b_logits, self._b_h
            g_valid = True
        else:
            g_tokens, g_logits, g_h = self._cold_broadcast(pubs_arr[0], n_samples)
            g_valid = False

        eval_args = ()
        if has_eval:
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

        chan_ops = ()
        if has_chan:
            chan_ops = _channel_scan_ops(channel_scan, num_rounds) + (
                jnp.asarray(np.asarray(sels), jnp.int32),  # (R, n_real)
            )
        driver = self._hetero_rounds_driver(
            k_cap, send_h, num_rounds, n_real, caps, has_eval, has_chan
        )
        carry, taps = driver(
            tuple(fleet_loras), tuple(fleet_opts),
            self._s_lora, self._s_opt, tuple(frozens), self._s_frozen,
            g_tokens, g_logits, g_h, jnp.asarray(g_valid),
            tuple(gathers), tuple(scatters), tuple(kss_b), tuple(batches_b),
            kss_all, pubs_arr, chan_ops, *eval_args,
        )
        (out_loras, out_opts, self._s_lora, self._s_opt,
         self._b_tokens, self._b_logits, self._b_h, _valid, _chan) = carry
        for be, lora, opt in zip(self._b, out_loras, out_opts):
            n = jax.tree.leaves(be._lora)[0].shape[0]
            be._lora = jax.tree.map(lambda x: x[:n], lora)
            be._opt = jax.tree.map(lambda x: x[:n], opt)
        self._d_loss = taps["distill_loss"][-1]

        def _tolist(name):
            return [float(x) for x in np.asarray(taps[name])]

        family_acc = client_acc = None
        if has_eval:
            fam = np.asarray(taps["family_client_acc"])  # (R, F)
            family_acc = [[float(a) for a in row] for row in fam]
            client_acc = [
                family_acc[r][first_bucket[r]] for r in range(num_rounds)
            ]
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
            client_acc=client_acc,
            family_client_acc=family_acc,
            snr_db=snr_db,
            outage=outage,
        )

    @staticmethod
    def _zero_batch_shapes(be: BatchedEngine) -> dict:
        """Per-sample batch shapes/dtypes of one bucket, WITHOUT consuming
        any client's rng stream (probed from the dataset layout)."""
        c = be.clients[0]
        seq_len = int(c.data.tokens.shape[1])
        bsz = c.batch_size  # epoch_batches always pads up to a full batch
        return {
            "tokens": ((be.local_steps, bsz, seq_len), c.data.tokens.dtype),
            "labels": ((be.local_steps, bsz), c.data.labels.dtype),
        }
