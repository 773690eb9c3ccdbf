"""FL server: sparse-logit aggregation + LLM distillation + broadcast
(Algorithm 1, server block: lines 1-2, 13-16)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.aggregation import AggregationMode, aggregate, aggregate_wire
from repro.core.protocol import downlink_bits
from repro.core.topk import SparseWire, densify
from repro.fed import steps as fed_steps
from repro.fed.client import ClientUpload
from repro.models import init as model_init

__all__ = ["Server"]


class Server:
    def __init__(
        self,
        cfg: ModelConfig,
        *,
        seed: int = 42,
        distill_lr: float = 1e-3,
        temperature: float = 2.0,
        lam: float = 0.03,
        aggregation: AggregationMode = "adaptive",
        distill_steps: int = 2,
        use_kernels: bool = False,
        restrict_to_support: bool = False,
        initial_params=None,
    ):
        self.cfg = cfg
        self.aggregation: AggregationMode = aggregation
        self.distill_steps = distill_steps
        self.use_kernels = use_kernels
        self.params = initial_params if initial_params is not None else model_init(jax.random.PRNGKey(seed), cfg)
        self.opt = fed_steps.init_lora_opt(self.params, cfg)
        self._distill_step = fed_steps.make_distill_step(
            cfg, lr=distill_lr, temperature=temperature, lam=lam,
            restrict_to_support=restrict_to_support,
        )

    # ---- Algorithm 1, line 15: aggregate client knowledge ----
    def aggregate_uploads(self, uploads: list[ClientUpload]):
        """Returns (K_g (P, V), h_g (P, r) or None)."""
        stack = jnp.stack([densify(u.sparse) for u in uploads])  # (N, P, V)
        hs = [u.h for u in uploads if u.h is not None]
        return self.aggregate_dense(stack, jnp.stack(hs) if hs else None)

    def aggregate_dense(
        self,
        stack: jax.Array,
        h_stack: jax.Array | None = None,
        *,
        mask: jax.Array | None = None,
    ):
        """Aggregate an already-densified (N, P, V) stack (+ optional (N, P, r)
        projection stack) — the batched engine's path; only clients that
        actually transmitted may appear in the stack (dropped stragglers are
        excluded, never zero-padded in).  ``mask`` is the optional explicit
        (N, P, V) transmit mask; without it "transmitted" falls back to the
        ``!= 0`` sentinel (which cannot see transmitted true zeros — see
        :mod:`repro.core.aggregation`)."""
        k_g = aggregate(stack, self.aggregation, mask=mask, use_kernel=self.use_kernels)
        h_g = jnp.mean(h_stack, axis=0) if h_stack is not None else None
        return k_g, h_g

    def aggregate_sparse_wire(
        self,
        wire: SparseWire,
        h_stack: jax.Array | None = None,
        *,
        validate: bool = False,
        budget_bits=None,
        value_bits: int = 16,
    ):
        """Aggregate straight from the sparse (values, indices, mask) wire
        format — O(N·P·k_cap) working set, no densified stack (the fused-e2e
        engine runs this same math inside its compiled round; this entry
        point serves callers holding a wire payload outside it).

        ``validate=True`` runs the server-side integrity gate
        (:func:`repro.core.faults.validate_wire`: non-finite values,
        out-of-range indices, and — with ``budget_bits`` — fits-violating
        byte counts) and quarantines offending client rows through the
        transmit-mask pattern before aggregating; their ``h`` rows are
        excluded from the projection mean too."""
        if validate:
            from repro.core.faults import quarantine_wire, validate_wire

            ok, _reasons = validate_wire(
                wire, value_bits=value_bits, budget_bits=budget_bits
            )
            if not bool(np.all(ok)):
                wire = quarantine_wire(wire, ok)
                if h_stack is not None:
                    keep = np.flatnonzero(ok)
                    h_stack = h_stack[jnp.asarray(keep)] if len(keep) else None
        k_g = aggregate_wire(wire, self.aggregation, use_kernel=self.use_kernels)
        h_g = jnp.mean(h_stack, axis=0) if h_stack is not None else None
        return k_g, h_g

    # ---- Algorithm 1, line 16: update the LLM by distilling K_g, h_g ----
    def distill(self, public_tokens, k_g, h_g) -> dict:
        metrics = {}
        for _ in range(self.distill_steps):
            self.params, self.opt, metrics = self._distill_step(
                self.params, self.opt, public_tokens, k_g, h_g
            )
        return {k: float(v) for k, v in metrics.items()}

    # ---- §II-B: broadcast the server's own refreshed knowledge ----
    def broadcast(self, public_tokens) -> tuple[jax.Array, jax.Array | None, int]:
        """Returns (K_down, h_down, downlink_bits).  The paper's workflow:
        after the server-side distillation update, the server re-infers the
        public set and broadcasts its logits + LoRA projection."""
        logits, h = fed_steps.public_logits(self.params, self.cfg, public_tokens)
        rank = self.cfg.lora.rank if (self.cfg.lora is not None and h is not None) else None
        bits = downlink_bits(logits.shape[0], logits.shape[-1], rank)
        return logits, h, bits
