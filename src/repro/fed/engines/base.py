"""Shared engine plumbing: budget math, round dataclasses, the sequential
reference engine, and the server-owner mixin.

See :mod:`repro.fed.engines` for the package overview.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.channel import BatchedChannelState, ChannelState, topk_budget_batch
from repro.core.protocol import UplinkPayload, downlink_bits, lora_projection_bits
from repro.core.topk import QUANT_LEVELS, QuantizedWire, SparseWire, densify
from repro.fed.client import Client
from repro.lora import merge_lora, split_lora

__all__ = [
    "BroadcastState",
    "ClientPhase",
    "RoundsTrajectory",
    "SequentialEngine",
    "tree_stack",
    "k_cap_bucket",
    "cohort_budgets",
    "check_unique_cohort",
    "fake_quant_dense",
    "shared_frozen_backbone",
]


def cohort_budgets(
    states,
    cfg: ModelConfig,
    n_samples: int,
    adaptive_k: bool,
    n_cohort: int,
    send_h: bool = False,
    *,
    value_bits: int = 16,
    k_min: int = 1,
    quantize_wire: bool = False,
) -> list[int]:
    """Per-client adaptive k for a cohort — ONE host-side scalar routine
    shared by every engine (and by the fault layer, which must price
    attempted uploads with exactly the engines' k math so HARQ retries and
    quarantine decisions can never drift from what the engine transmits).

    With ``send_h`` the LoRA-projection bits are reserved out of each
    budget first (see :meth:`repro.fed.client.Client.upload`).  Under
    ``quantize_wire`` the (value, index) entries are priced at 8 value
    bits — the same Shannon budget genuinely affords a larger k — while
    the unquantized projection stays at ``value_bits``.
    """
    if not adaptive_k:
        return [cfg.vocab_size] * n_cohort
    reserved = (
        lora_projection_bits(n_samples, cfg.lora.rank, value_bits)
        if (send_h and cfg.lora is not None)
        else 0
    )
    wire_bits = 8 if quantize_wire else value_bits
    return topk_budget_batch(
        states, vocab_size=cfg.vocab_size, num_samples=n_samples,
        value_bits=wire_bits, k_min=k_min, reserved_bits=reserved,
    )


def k_cap_bucket(ks: Sequence[int], vocab: int) -> int:
    """Static sparse-wire width for a round: the next power of two >=
    max(ks), clamped to the vocabulary.  Bucketing keeps the number of
    distinct compiled round executables at O(log2 V) while the adaptive
    budgets themselves stay DATA (the transmit mask)."""
    need = max([k for k in ks] + [1])
    cap = 1
    while cap < need:
        cap *= 2
    return min(cap, vocab)


def check_unique_cohort(sel: Sequence[int]) -> list[int]:
    """Validate a USER-provided cohort selection at the engine boundary.

    The engines' scatter-back is ``.at[sel].set`` (and the host store's
    row writes), where duplicate indices resolve in UNSPECIFIED order —
    a silently nondeterministic fleet.  The internal shard-padding path
    (:meth:`FusedEngine._pad_cohort`) intentionally appends duplicate
    rows AFTER this check and discards their advanced state before any
    write-back, so it stays legal.  Returns the selection as ints."""
    out = [int(i) for i in sel]
    if len(set(out)) != len(out):
        dups = sorted({i for i in out if out.count(i) > 1})
        raise ValueError(
            f"cohort selection contains duplicate client ids {dups}: the "
            "scatter-back (.at[sel].set) would resolve duplicate rows in "
            "unspecified order — select each client at most once per round"
        )
    return out


def _channel_scan_ops(channel_scan: dict, num_rounds: int) -> tuple:
    """Validate + device-stage a ``scan_channel_inputs`` dict for the
    multi-round drivers: (z0, bad0, w, u, base_snr_db, rho, p_gb, p_bg,
    fade_scale).  Every element is DATA — the drivers compile one channel
    program for all scenarios."""
    try:
        w = np.asarray(channel_scan["w"])
    except KeyError as e:
        raise ValueError(f"channel_scan is missing key {e}") from None
    if w.ndim != 2 or w.shape[0] < num_rounds:
        raise ValueError(
            f"channel_scan covers {w.shape[0] if w.ndim == 2 else '?'} "
            f"rounds, need {num_rounds} "
            "(ChannelSimulator.scan_channel_inputs(num_rounds))"
        )
    return (
        jnp.asarray(channel_scan["z0"], jnp.float32),
        jnp.asarray(channel_scan["bad0"], bool),
        jnp.asarray(w[:num_rounds], jnp.float32),
        jnp.asarray(np.asarray(channel_scan["u"])[:num_rounds], jnp.float32),
        jnp.asarray(
            np.asarray(channel_scan["base_snr_db"])[:num_rounds], jnp.float32
        ),
        jnp.asarray(channel_scan["rho"], jnp.float32),
        jnp.asarray(channel_scan["p_gb"], jnp.float32),
        jnp.asarray(channel_scan["p_bg"], jnp.float32),
        jnp.asarray(channel_scan["fade_scale"], jnp.float32),
    )


def fake_quant_dense(dense: jax.Array) -> jax.Array:
    """Quantize-dequantize a densified top-k stack through the int8 wire's
    per-(client, sample)-row symmetric code — what the dense-path engines
    (batched/fused client phase) apply under ``quantize_wire`` so their
    uplink carries exactly the values the 8-bit-per-entry ledger prices.
    Zeros (off-support entries) map to exact zeros, so the support is
    preserved."""
    amax = jnp.max(jnp.abs(dense), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / QUANT_LEVELS, 1.0)
    q = jnp.clip(jnp.round(dense / scale), -QUANT_LEVELS, QUANT_LEVELS)
    return q * scale


def tree_stack(trees: Sequence) -> object:
    """Stack a list of identically-structured pytrees along a new leading
    (client) axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def shared_frozen_backbone(frozens: Sequence) -> bool:
    """True iff every client's frozen tree is literally the same arrays —
    the paper's setting (one pretrained W' under per-client LoRA deltas).
    Identity, not value comparison: O(leaves), no device work."""
    first = jax.tree.leaves(frozens[0])
    for other in frozens[1:]:
        leaves = jax.tree.leaves(other)
        if len(leaves) != len(first) or any(a is not b for a, b in zip(first, leaves)):
            return False
    return True


@dataclasses.dataclass(frozen=True)
class BroadcastState:
    """The server's knowledge broadcast carried across rounds (Fig. 1 step 1).

    Replaces the fragile ``pub_tokens_prev`` / ``g_bits`` forward references:
    the public tokens the knowledge was computed on travel *with* the logits
    they explain, and the downlink cost is accounted from the same object.
    """

    tokens: jax.Array  # (P, L) public batch the knowledge was inferred on
    logits: jax.Array  # (P, V) global logits K_g
    h: jax.Array | None  # (P, r) global LoRA projection h_g
    bits: int  # on-air size of one broadcast to one client


@dataclasses.dataclass
class ClientPhase:
    """Result of one round's client phase, engine-agnostic.

    ``dense``/``h`` hold only the ``num_transmitters`` clients that actually
    uploaded (leading axis), in cohort order; ``ks`` covers every *selected*
    client (0 marks a dropped straggler).  The fused-e2e engine reports the
    uplink as the sparse wire format instead (``sparse``; ``dense`` stays
    None — no (T, P, V) stack exists on that path).
    """

    dense: jax.Array | None  # (T, P, V) densified top-k logits
    h: jax.Array | None  # (T, P, r) LoRA projections
    payloads: list[UplinkPayload]
    ks: list[int]
    # (T, P, k_cap) wire — QuantizedWire under the engines' quantize_wire
    sparse: SparseWire | QuantizedWire | None = None

    @property
    def uplink_bytes(self) -> float:
        return float(sum(p.bytes for p in self.payloads))

    @property
    def num_transmitters(self) -> int:
        return len(self.payloads)


@dataclasses.dataclass
class RoundsTrajectory:
    """Per-round observables of one :meth:`FusedE2EEngine.run_rounds` block.

    ``ks``/``payloads`` are the host-side accounting (identical to what R
    ``run_round`` calls report); ``mean_k``, ``distill_loss`` and — when
    eval data was passed — ``server_acc``/``client_acc`` come from the
    IN-SCAN eval tap: they are scanned outputs of the single compiled
    multi-round dispatch, not host round-trips.  ``distill_loss`` is the
    round's final server-distill step loss (NaN for an all-dropped round —
    the server never distilled).

    Heterogeneous blocks (:meth:`HeteroFusedE2EEngine.run_rounds`)
    additionally fill ``family_client_acc``: per round, one accuracy per
    family bucket (fleet bucket order), each evaluated on that bucket's
    first selected client of the round (or its bucket-local client 0 when
    the family sat the round out).  ``client_acc`` remains the cohort's
    first selected client — the host loop's metric — which is always one of
    those family entries.
    """

    ks: list[list[int]]
    payloads: list[list[UplinkPayload]]
    mean_k: list[float]
    distill_loss: list[float]
    server_acc: list[float] | None = None
    client_acc: list[float] | None = None
    family_client_acc: list[list[float]] | None = None
    # Scenario runs only (``channel_scan`` passed): the in-scan channel
    # replica's per-round realised cohort SNR (dB, -inf in outage) and
    # Gilbert-Elliott outage flags — scanned outputs of the same compiled
    # dispatch, evolved from the channel carry (f32 replica of the host
    # realisation that priced ``ks``/``payloads``).
    snr_db: list[list[float]] | None = None
    outage: list[list[bool]] | None = None


class SequentialEngine:
    """Reference client-phase executor: one client at a time (Algorithm 1
    exactly as written)."""

    name = "sequential"
    store_kind = "device"  # per-client params live on device, unstacked

    def __init__(
        self,
        clients: list[Client],
        cfg: ModelConfig,
        *,
        value_bits: int = 16,
        k_min: int = 1,
        **_unused,
    ):
        self.clients = clients
        self.cfg = cfg
        self.value_bits = value_bits
        self.k_min = k_min

    def client_params(self, cid: int):
        """Current parameters of one client (for evaluation)."""
        return self.clients[cid].params

    def fleet_state(self) -> dict:
        """The whole fleet's trainable state as one checkpointable pytree.
        Per-client subtrees (not a stacked axis): the sequential engine
        serves mixed-architecture fleets natively, so client leaves need
        not share shapes."""
        return {
            f"client{i}": {"params": c.params, "opt": c.opt}
            for i, c in enumerate(self.clients)
        }

    def load_fleet_state(self, state: dict) -> None:
        for i, c in enumerate(self.clients):
            c.params = jax.tree.map(jnp.asarray, state[f"client{i}"]["params"])
            c.opt = jax.tree.map(jnp.asarray, state[f"client{i}"]["opt"])

    def prefetch_cohort(self, sel: Sequence[int]) -> None:
        """No-op: every client's state already lives on device."""

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
        if bcast is not None:
            for c in cohort:
                c.local_distill(bcast.tokens, bcast.logits, bcast.h)
        dense_rows, hs, payloads, ks = [], [], [], []
        for c, st in zip(cohort, states):
            c.local_train()
            up = c.upload(
                pub_tokens,
                st,
                value_bits=self.value_bits,
                k_override=None if adaptive_k else self.cfg.vocab_size,
                send_h=send_h,
                k_min=self.k_min,
            )
            if up is None:  # straggler in outage: transmits nothing
                ks.append(0)
                continue
            ks.append(up.k)
            dense_rows.append(densify(up.sparse))
            if up.h is not None:
                hs.append(up.h)
            payloads.append(up.payload)
        return ClientPhase(
            dense=jnp.stack(dense_rows) if dense_rows else None,
            h=jnp.stack(hs) if hs else None,
            payloads=payloads,
            ks=ks,
        )


class _ServerOwnerMixin:
    """Server-state plumbing shared by the end-to-end engines (homogeneous
    :class:`FusedE2EEngine` and bucketed :class:`HeteroFusedE2EEngine`):
    they own the server LLM's state for the duration of a run, compute the
    broadcast in-program, and sync back for evaluation/checkpointing.

    Expects the owner to maintain ``server``, ``_s_lora``/``_s_frozen``/
    ``_s_opt``, the broadcast carry ``_b_tokens``/``_b_logits``/``_b_h``
    and the observability tap ``_d_loss``.
    """

    handles_server = True

    def _init_server_state(self, server) -> None:
        self.server = server
        self._s_lora, self._s_frozen = split_lora(server.params)
        self._s_opt = server.opt
        # broadcast knowledge computed in-program, carried across rounds
        self._b_tokens: jax.Array | None = None
        self._b_logits: jax.Array | None = None
        self._b_h: jax.Array | None = None
        self._d_loss: jax.Array | None = None

    def _cold_broadcast(self, pub_tokens: jax.Array, n_samples: int):
        """Round-0 placeholder g_* operands (same arg structure as a warm
        round; ``g_valid=False`` discards their effect in-program)."""
        g_logits = jnp.zeros((n_samples, self.server.cfg.vocab_size), jnp.float32)
        if self.server.cfg.lora is not None:
            g_h = jnp.zeros((n_samples, self.server.cfg.lora.rank), jnp.float32)
        else:
            g_h = None
        return pub_tokens, g_logits, g_h

    def broadcast_state(self, pub_tokens: jax.Array) -> BroadcastState:
        """The in-program-refreshed broadcast of the LAST executed round, as
        the host-side carrier (byte accounting identical to
        :meth:`repro.fed.server.Server.broadcast`)."""
        assert self._b_logits is not None, "no round has run yet"
        rank = (
            self.server.cfg.lora.rank
            if (self.server.cfg.lora is not None and self._b_h is not None)
            else None
        )
        bits = downlink_bits(
            int(self._b_logits.shape[0]), int(self._b_logits.shape[-1]), rank
        )
        return BroadcastState(
            tokens=pub_tokens, logits=self._b_logits, h=self._b_h, bits=bits
        )

    @property
    def last_distill_loss(self) -> float:
        """The final server-distill step loss of the last executed round
        (computed in-program; NaN before any round ran or for an all-dropped
        round)."""
        return float("nan") if self._d_loss is None else float(self._d_loss)

    def sync_server(self) -> None:
        """Materialise the engine-held server state back onto the Server
        object (for evaluation / checkpointing)."""
        self.server.params = merge_lora(self._s_lora, self._s_frozen)
        self.server.opt = self._s_opt

    def server_state(self) -> dict:
        """The engine-held server state as one checkpointable pytree."""
        return {
            "s_lora": self._s_lora,
            "s_frozen": self._s_frozen,
            "s_opt": self._s_opt,
        }

    def load_server_state(self, state: dict) -> None:
        as_jax = lambda tree: jax.tree.map(jnp.asarray, tree)  # noqa: E731
        self._s_lora = as_jax(state["s_lora"])
        self._s_frozen = as_jax(state["s_frozen"])
        self._s_opt = as_jax(state["s_opt"])
        self.sync_server()

    def load_broadcast(self, tokens, logits, h=None) -> None:
        """Restore the in-program broadcast carry (the knowledge the NEXT
        round's cohort distills against) from a checkpoint."""
        self._b_tokens = jnp.asarray(tokens)
        self._b_logits = jnp.asarray(logits)
        self._b_h = None if h is None else jnp.asarray(h)
